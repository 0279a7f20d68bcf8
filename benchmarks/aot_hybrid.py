"""benchmarks/aot.py's lowering for the runner benchmarks/train_hybrid.py:
the TrainStep program of a Qwen3-Next configuration for a chip that is
described, not attached. aot.lower_train_step builds the Llama block
(`zero_model`), so the step is assembled here again around this runner's
model; the device patch and the abstract arguments are aot.py's own.
"""
from __future__ import annotations

from benchmarks import aot


def zero_model(cfg, **extra):
    """The program's model at the configuration's shapes, weights zero."""
    from benchmarks import train_hybrid as T
    from paddle_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
    from paddle_tpu.nn import initializer as I

    with I.param_init_override(I.Constant(0.0), dtype=cfg["torch_dtype"]):
        return Qwen3NextForCausalLM(
            Qwen3NextConfig(**T.model_config(cfg, **extra)))


def lower_train_step(cfg, batch, seq, sharding):
    """The TrainStep program of benchmarks/train_hybrid.py at [batch, seq]."""
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.core import random as random_mod
    from paddle_tpu.optimizer.optimizer import _found_inf_operand

    tr = cfg["train"]
    o = tr["optimizer"]
    model = zero_model(cfg, recompute=tr["recompute"],
                       fused_loss_chunk=tr["fused_loss_chunk"])
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
    with aot.as_on_tpu():
        return step._build().trace(*aot._abstract(args, sharding)).lower(
            lowering_platforms=("tpu",))
