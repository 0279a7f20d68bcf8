"""benchmarks/aot_hybrid.py's lowering for the runner
benchmarks/train_ling3.py: the TrainStep program of a Ling 3.0
configuration for a chip that is described, not attached. The step is
assembled by aot_hybrid.lower_train_step, which reads the same keys of the
configuration's `train` group; only the model it is assembled around is
this runner's, put in the place of aot_hybrid's `zero_model` for the call.
"""
from __future__ import annotations

from unittest import mock

from benchmarks import aot_hybrid


def zero_model(cfg, **extra):
    """The program's model at the configuration's shapes, weights zero."""
    from benchmarks import train_ling3 as T
    from paddle_tpu.models import Ling3Config, Ling3ForCausalLM
    from paddle_tpu.nn import initializer as I

    with I.param_init_override(I.Constant(0.0), dtype=cfg["torch_dtype"]):
        return Ling3ForCausalLM(Ling3Config(**T.model_config(cfg, **extra)))


def lower_train_step(cfg, batch, seq, sharding):
    """The TrainStep program of benchmarks/train_ling3.py at
    [batch, seq]."""
    with mock.patch.object(aot_hybrid, "zero_model", zero_model):
        return aot_hybrid.lower_train_step(cfg, batch, seq, sharding)
