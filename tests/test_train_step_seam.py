"""The seam between backward and optimizer in jit.TrainStep (PERF.md, PR
32): on the plain path every live gradient crosses from the backward pass
to the optimizer through a `jax.lax.optimization_barrier` of its own, so
that XLA cannot pull the optimizer's elementwise update into the epilogue
of the weight-gradient matmul that produced the gradient. Before it, every
`dW = x^T dy` of a step carried the AdamW update of that weight's bf16 copy
on each output tile and ran at 39 to 53 % of the MXU's peak where the same
step's forward matmuls ran near 90 %.

Three witnesses: the count of barriers in the traced step, the numbers
(the barrier is the identity), and the compiled module's text for a
described v5e (nothing runs; tests/test_granite_hybrid_layout.py is the
pattern). The topology is described inside a fixture; where none can be
described that test skips.
"""
import os
import re
from unittest import mock

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn


def _model():
    """Two Linears and a norm: six live leaves."""
    paddle.seed(7)
    return nn.Sequential(nn.Linear(8, 16), nn.LayerNorm(16), nn.Linear(16, 4))


def _loss_fn(model, x, y):
    d = model(x) - y
    return (d * d).mean()


def _data():
    rng = np.random.RandomState(0)
    return (paddle.to_tensor(rng.randn(32, 8).astype(np.float32)),
            paddle.to_tensor(rng.randn(32, 4).astype(np.float32)))


def _adamw_master(model):
    model.to(dtype="bfloat16")
    return paddle.optimizer.AdamW(
        learning_rate=0.05, weight_decay=0.1, parameters=model.parameters(),
        multi_precision=True)


def _sgd(model):
    return paddle.optimizer.SGD(learning_rate=0.1,
                                parameters=model.parameters())


def _no_barrier():
    import jax

    return mock.patch.object(jax.lax, "optimization_barrier", lambda x: x)


@pytest.mark.parametrize("accum_steps,per_leaf", [(1, 1), (2, 0)])
def test_one_barrier_a_live_leaf_on_the_plain_path(accum_steps, per_leaf):
    model = _model()
    step = paddle.jit.TrainStep(model, _loss_fn, _adamw_master(model),
                                donate=False, accum_steps=accum_steps)
    x, y = _data()
    x, y = x.astype("bfloat16"), y.astype("bfloat16")
    args = step._prepare((x, y), {})  # builds the step
    text = step._compiled.lower(*args).as_text()  # finds the live leaves
    live = len(step._live_idx)
    assert live == 6  # two weights, two biases, the norm's scale and shift
    assert text.count("optimization_barrier") == per_leaf * live
    # a barrier a leaf, never one over the list: each holds one operand
    for operands in re.findall(r"optimization_barrier\s+([^\n:]*):", text):
        assert "," not in operands, operands


def _three_steps(make_opt, donate, cast):
    model = _model()
    opt = make_opt(model)
    step = paddle.jit.TrainStep(model, _loss_fn, opt, donate=donate)
    data = tuple(a.astype(cast) for a in _data())
    losses = [step(*data).numpy().astype(np.float32) for _ in range(3)]
    params = [p.numpy().astype(np.float32) for p in model.parameters()]
    states = [{k: np.asarray(v, np.float32)
               for k, v in sorted(opt._accumulators[id(p)].items())}
              for p in model.parameters()]
    return losses, params, states


@pytest.mark.parametrize("donate", [False, True])
@pytest.mark.parametrize("make_opt,cast,rtol", [
    (_adamw_master, "bfloat16", 2e-2), (_sgd, "float32", 1e-4)])
def test_the_barrier_changes_no_number(make_opt, cast, rtol, donate):
    losses, params, states = _three_steps(make_opt, donate, cast)
    with _no_barrier():
        plain = _three_steps(make_opt, donate, cast)
    # bit for bit: the barrier is the identity
    np.testing.assert_array_equal(losses, plain[0])
    for a, b in zip(params, plain[1]):
        np.testing.assert_array_equal(a, b)
    assert len(states) == len(plain[2])
    for a, b in zip(states, plain[2]):
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])

    # and the staged step is the eager sequence (tests/test_jit.py's
    # tolerance in float32; bf16 leaves round each step)
    model = _model()
    opt = make_opt(model)
    data = tuple(a.astype(cast) for a in _data())
    eager = []
    for _ in range(3):
        loss = _loss_fn(model, *data)
        loss.backward()
        opt.step()
        opt.clear_grad()
        eager.append(loss.numpy().astype(np.float32))
    np.testing.assert_allclose(losses, eager, rtol=rtol)
    for a, p in zip(params, model.parameters()):
        np.testing.assert_allclose(a, p.numpy().astype(np.float32),
                                   rtol=rtol, atol=rtol * 1e-1)


# ------------------------------------------------- compiled for a v5e
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
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compiled_step(sharding):
    """One Linear at the Mistral MLP's widths in bf16 under AdamW with
    master weights, its step over [4, 4096] rows compiled for the chip."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat

    with nn.initializer.param_init_override(
            lambda shape, dtype=None: jnp.zeros(shape, jnp.bfloat16)):
        model = nn.Linear(4096, 14336, bias_attr=False)
    opt = paddle.optimizer.AdamW(
        learning_rate=3e-4, weight_decay=0.1, parameters=model.parameters(),
        multi_precision=True)

    def loss_fn(m, x):
        y = m(x).astype("float32")
        return (y * y).mean()

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    args = step._prepare(
        (paddle.to_tensor(jnp.zeros((4, 4096, 4096), jnp.bfloat16)),), {})
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)
    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        return step._compiled.trace(*abstract).lower(
            lowering_platforms=("tpu",)).compile()


def _matmuls_with_optimizer_work(text):
    """The fused computations of a compiled module that hold both a
    matmul (a `convolution` on a TPU) and an instruction of the optimizer,
    constants aside: XLA shares a `constant(1)` that carries the
    optimizer's label with matmul fusions, and that is no optimizer work."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"%(fused_computation\S*) ", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            bodies[name].append(line)
    assert bodies
    return [name for name, lines in bodies.items()
            if any(" convolution(" in line for line in lines)
            and any("/optimizer/" in line and " constant(" not in line
                    for line in lines)]


def _total_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_no_weight_gradient_matmul_carries_optimizer_work(
        one_chip, no_persistent_cache):
    seamed = _compiled_step(one_chip)
    with _no_barrier():
        fused = _compiled_step(one_chip)
    assert seamed.as_text().count(" convolution(") == 2  # y and dW
    assert _matmuls_with_optimizer_work(seamed.as_text()) == []
    # the census sees what the seam is for: without it the dW matmul's
    # epilogue is the update of the weight's bf16 copy (were this to read
    # 0 one day, XLA no longer fuses them and the seam can go)
    assert len(_matmuls_with_optimizer_work(fused.as_text())) == 1
    # the gradient was a materialised output before: no memory is added
    assert abs(_total_bytes(seamed) / _total_bytes(fused) - 1) < 0.01
