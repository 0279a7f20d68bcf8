"""A census of one MLA block (models/deepseek_v3.py) at the published
widths and the shape `kanana2.pretrain-8k` runs it at, forward and backward
under jax.checkpoint, compiled ahead of time for a described v5e (nothing
runs): its core is the three MLA kernels under their own names, the shared
rotary key reaches them as ONE head, [batch, seq, 64], and is never
broadcast to the 32 heads ([batch, seq, 32, 64] written and read 32 times
over, forward and backward), no head is padded to 256 columns, and the
rotary columns' interleave is undone on the weights (no gather on an
activation).

The topology is described inside a fixture, as tests/benchmarks' ahead-of-
time tests do; where none can be described the test skips.
"""
import os
import re
from unittest import mock

import pytest

BATCH, SEQ = 2, 8192


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


def _compiled_block(sharding):
    """One DeepseekV3Attention at the published widths in bf16: the
    gradient of its recomputed forward in all parameters and the input."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat
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
            with paddle.no_grad():
                return layer(paddle.to_tensor(x))._data
        finally:
            for p, a in zip(params, old):
                p._data = a

    def step(arrays, x):
        return jax.grad(lambda arrays, x: jnp.sum(
            jax.checkpoint(block)(arrays, x).astype(jnp.float32)),
            argnums=(0, 1))(arrays, x)

    abstract = lambda shape: jax.ShapeDtypeStruct(
        tuple(shape), jnp.bfloat16, sharding=sharding)
    args = ([abstract(p.shape) for p in params],
            abstract((BATCH, SEQ, cfg.hidden_size)))
    # the TPU's branch: the kernels, through Mosaic and not the interpreter
    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        return jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()


def test_the_shared_rotary_key_is_one_head_and_no_head_is_padded(
        one_chip, no_persistent_cache):
    text = _compiled_block(one_chip).as_text()
    calls = re.findall(
        r"%\w*?(mla_attention_(?:fwd|bwd_dq|bwd_dkv))[_.\d]* = (.*?) "
        r"custom-call\(.*?operand_layout_constraints=\{(.*?)\}, frontend_attr",
        text)
    # the recomputed forward and the two backward kernels (the gradient
    # alone is asked for, so the first forward is dead code)
    assert sorted(name for name, _, _ in calls) == [
        "mla_attention_bwd_dkv", "mla_attention_bwd_dq",
        "mla_attention_fwd"]
    assert "flash_attention" not in text
    for name, results, operands in calls:
        # q_nope, q_rope, k_nope, the ONE rotary key, v
        assert operands.startswith(
            f"bf16[64,{SEQ},128]{{2,1,0}}, bf16[64,{SEQ},64]{{2,1,0}}, "
            f"bf16[64,{SEQ},128]{{2,1,0}}, bf16[{BATCH},{SEQ},64]{{2,1,0}}, "
            f"bf16[64,{SEQ},128]{{2,1,0}}"), (name, operands)
        if name.endswith("dkv"):      # dk_nope, dk_rope summed, dv
            assert re.findall(r"bf16\[([\d,]+)\]", results) == [
                f"64,{SEQ},128", f"{BATCH},{SEQ},64", f"64,{SEQ},128"]
    # nowhere in the module: the key broadcast to the heads (any order of
    # the axes), or a head padded to 256 (or joined to 192) columns
    for shape in (f"{BATCH},{SEQ},32,256", f"{BATCH},32,{SEQ},256",
                  f"64,{SEQ},256", f"64,{SEQ},192",
                  f"{BATCH},{SEQ},32,192", f"{BATCH},32,{SEQ},192"):
        assert f"[{shape}]" not in text, shape
    broadcasts = re.findall(
        r"= \w+\[(?:%d,%d,32,64|%d,32,%d,64|64,%d,64)\]\S* broadcast\("
        % (BATCH, SEQ, BATCH, SEQ, SEQ), text)
    assert not broadcasts, broadcasts
    # the interleave is undone on the weights: no gather on an activation
    gathers = [dims for dims in re.findall(
        r"= \w+\[([\d,]+)\]\S* gather\(", text) if str(SEQ) in dims]
    assert not gathers, gathers
