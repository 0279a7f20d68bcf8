"""Lower the cells' programs for a chip that is described, not attached.

The sandbox has the TPU's compiler and no TPU. These helpers hand the
program's own step functions abstract arguments placed on a described
device, with the program's one device test patched to the TPU side so that
the kernels go through Mosaic. Nothing runs; what comes back is a
jax.stages.Lowered whose .compile() raises what the chip's compiler would
and whose memory_analysis() sizes a batch. Used by
tests/benchmarks/test_benchmark_aot.py and when a configuration is sized.
"""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def as_on_tpu():
    """paddle_tpu asks jax.default_backend() in one place
    (core.device.on_tpu); the names that were bound from it are patched
    where they are used."""
    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat

    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        yield


def _abstract(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def lower_train_step(cfg, batch, seq, sharding):
    """The TrainStep program of benchmarks/train.py at [batch, seq]."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import random as random_mod
    from paddle_tpu.optimizer.optimizer import _found_inf_operand

    tr = cfg["train"]
    o = tr["optimizer"]
    model = zero_model(cfg, fused_loss_chunk=tr["fused_loss_chunk"])
    opt = paddle.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"], beta2=o["beta2"],
        epsilon=o["epsilon"], weight_decay=o["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m(ids, labels=ids)[1], opt)
    states = [opt._ensure_state(p) for p in step._params]
    step._out_shardings = tuple(
        opt._param_out_sharding(p._data, st)
        for p, st in zip(step._params, states))
    step._grad_shardings = None
    step._cur_nan_key = None
    ids = jnp.zeros((batch, seq), jnp.int32)
    args = (
        [p._data for p in step._params], [b._data for b in step._buffers],
        states, jnp.float32(opt.get_lr()), jnp.float32(1.0),
        _found_inf_operand(opt), random_mod.default_generator.split_key(),
        ((ids,), {}),
    )
    with as_on_tpu():
        return step._build().trace(*_abstract(args, sharding)).lower(
            lowering_platforms=("tpu",))


def zero_model(cfg, **extra):
    """The program's model at the configuration's shapes, weights zero:
    lowering needs shapes alone."""
    from benchmarks import train as T
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn import initializer as I

    with I.param_init_override(I.Constant(0.0), dtype=cfg["torch_dtype"]):
        return LlamaForCausalLM(LlamaConfig(**T.model_config(cfg, **extra)))


def lower_engine_program(cfg, kind, sharding, bucket=None):
    """One of the serving engine's programs (`decode`, `prefill`,
    `prefill_ext`) as benchmarks/serve.py's engine launches it."""
    import jax

    from paddle_tpu.serving import Engine, EngineConfig

    model = zero_model(cfg)
    model.eval()
    engine = Engine(model, EngineConfig(**cfg["engine"]))
    fn = engine._step_fns[kind]
    args = _abstract(engine._abstract_args(kind, bucket), sharding)
    # on the chip the engine donates the pool (arguments 1 and 2)
    specs = dict(engine._jit_specs[kind], donate_argnums=(1, 2))
    engine._pin_adapter()
    with as_on_tpu():
        return jax.jit(lambda *a: fn(*a), **specs).trace(
            *args, False).lower(lowering_platforms=("tpu",))
