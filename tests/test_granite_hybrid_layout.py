"""A layout census of the Mamba-2 mixer (models/granite_hybrid.py): one
layer at the published widths and the shape `granite4h.pretrain-8k` runs it
at, forward and backward under jax.checkpoint, compiled ahead of time for a
described v5e (nothing runs). From in_proj to out_proj every activation
stays [b, t, channels], the form the SSD kernels read (a head is 64
consecutive columns, dt, A and D per-head vectors the kernel spreads
itself), and x, B and C are convolved apart: so the compiled program holds
no copy, reshape or transpose that only moves an activation of the hidden
width or more. (The whole step, not this mixer alone, cuts x out of the
float32 in-projection by a `slice` of its own in some layers: PERF.md.)
tests/test_qwen3_next_layout.py is the pattern (PERF.md, PR 30: 27 such
instructions wrote 7.8 GB a DeltaNet layer before its contract).

The topology is described inside a fixture, as tests/benchmarks' ahead-of-
time tests do; where none can be described the test skips.
"""
import os
import re
from unittest import mock

import numpy as np
import pytest

BATCH, SEQ = 2, 8192
MOVES = ("copy", "reshape", "transpose")


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


def _compiled_mixer(sharding):
    """One GraniteHybridMamba at the published widths in bf16: the
    gradient of its recomputed forward in all parameters and the input.
    -> (the elements of its largest parameter, the compiled program)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.models import GraniteHybridConfig
    from paddle_tpu.models.granite_hybrid import GraniteHybridMamba

    cfg = GraniteHybridConfig()
    with paddle.nn.initializer.param_init_override(
            lambda shape, dtype=None: jnp.zeros(shape, jnp.bfloat16)):
        layer = GraniteHybridMamba(cfg)
    params = list(layer.parameters())

    def mixer(arrays, x):
        old = [p._data for p in params]
        for p, a in zip(params, arrays):
            p._data = a
        try:
            with paddle.no_grad():
                return layer(paddle.to_tensor(x))._data
        finally:
            for p, a in zip(params, old):
                p._data = a

    def step(arrays, x):
        return jax.grad(lambda arrays, x: jnp.sum(
            jax.checkpoint(mixer)(arrays, x).astype(jnp.float32)),
            argnums=(0, 1))(arrays, x)

    abstract = lambda shape: jax.ShapeDtypeStruct(
        tuple(shape), jnp.bfloat16, sharding=sharding)
    args = ([abstract(p.shape) for p in params],
            abstract((BATCH, SEQ, cfg.hidden_size)))
    # the TPU's branch: the kernels, through Mosaic and not the interpreter
    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        return max(int(np.prod(p.shape)) for p in params), jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()


def _outside_fusions(text):
    """The module's text less the bodies of its fused computations."""
    kept, fused = [], False
    for line in text.splitlines():
        if line.startswith("%fused_computation"):
            fused = True
        kept.append("" if fused else line)
        if line == "}":
            fused = False
    return "\n".join(kept)


def test_the_mixer_moves_no_activation_between_layouts(
        one_chip, no_persistent_cache):
    largest_weight, compiled = _compiled_mixer(one_chip)
    text = compiled.as_text()
    kernels = set(re.findall(r"%\w*?(mamba2_ssd_(?:fwd|bwd))", text))
    assert kernels == {"mamba2_ssd_fwd", "mamba2_ssd_bwd"}
    # every instruction of the module that is a copy, reshape or
    # transpose by itself (inside a fusion it costs no pass over HBM of
    # its own; a bitcast costs nothing) and writes an activation of the
    # hidden width or more: the projection's weight is smaller
    floor = BATCH * SEQ * 2048
    assert floor > largest_weight
    moved = [
        (name, op, dtype, dims) for name, dtype, dims, op in re.findall(
            r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\]\S* (\w+)\(",
            _outside_fusions(text), re.M)
        if op in MOVES and dims
        and np.prod([int(d) for d in dims.split(",")]) >= floor]
    assert not moved, moved
