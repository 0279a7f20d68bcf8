"""A census of one MLA block (models/deepseek_v3.py) at the published
widths and the shape `kanana2.pretrain-8k` runs it at, forward and backward
under jax.checkpoint, compiled ahead of time for a described v5e (nothing
runs): its core is the three MLA kernels under their own names, the shared
rotary key reaches them as ONE head, [batch, seq, 64], and is never
broadcast to the 32 heads ([batch, seq, 32, 64] written and read 32 times
over, forward and backward), no head is padded to 256 columns, and the
rotary columns' interleave is undone on the weights (no gather on an
activation). From the projections to `o_proj` the 128-wide parts (q_nope,
k_nope, v, out and their cotangents) stay [batch, seq, heads * 128], which
the kernels read in place: the compiled block moves no such activation
between layouts (before, 55 ms of a kanana-2 step: PERF.md, PR 36). And
`flash_attention`, which shares the kernels, hands them [batch * heads,
seq, d] as it did.

The topology is described inside a fixture, as tests/benchmarks' ahead-of-
time tests do; where none can be described the test skips.
"""
import os
import re
from unittest import mock

import numpy as np
import pytest

BATCH, SEQ, HEADS = 2, 8192, 32
MOVES = ("copy", "transpose", "slice", "concatenate")
# what is left of them: the 64-wide rotary query, merged to [b*h, s, 64]
# (half a lane tile is not read in place), and its cotangent in float32
ROTARY = {("copy", "bf16", f"{BATCH},{HEADS},{SEQ},64"),
          ("copy", "f32", f"{BATCH},{HEADS},{SEQ},64")}


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


@pytest.fixture(scope="module")
def block_text(one_chip):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return _compiled_block(one_chip).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def test_the_shared_rotary_key_is_one_head_and_no_head_is_padded(
        block_text):
    text = block_text
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
    flat = f"bf16[{BATCH},{SEQ},{HEADS * 128}]{{2,1,0}}"
    for name, results, operands in calls:
        # q_nope, k_nope, v flat; q_rope a head a row; the ONE rotary key
        assert operands.startswith(
            f"{flat}, bf16[{BATCH * HEADS},{SEQ},64]{{2,1,0}}, {flat}, "
            f"bf16[{BATCH},{SEQ},64]{{2,1,0}}, {flat}"), (name, operands)
        if name.endswith("dkv"):      # dk_nope, dk_rope summed, dv
            assert re.findall(r"bf16\[([\d,]+)\]", results) == [
                f"{BATCH},{SEQ},{HEADS * 128}", f"{BATCH},{SEQ},64",
                f"{BATCH},{SEQ},{HEADS * 128}"]
        if name.endswith("dq"):       # dq_nope flat, dq_rope, delta
            assert re.findall(r"(\w+)\[([\d,]+)\]", results) == [
                ("bf16", f"{BATCH},{SEQ},{HEADS * 128}"),
                ("bf16", f"{BATCH * HEADS},{SEQ},64"),
                ("f32", f"{BATCH * HEADS},8,{SEQ}")]
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


def test_no_128_wide_activation_moves_between_layouts(block_text):
    """Every instruction of the module that is a copy, transpose, slice or
    concatenate by itself (inside a fusion it costs no pass over HBM of its
    own; a bitcast costs nothing) of an activation of the heads' size or
    half of it: none of the 128-wide parts, and of the rotary parts the two
    ROTARY lists. The weights are smaller than the floor."""
    floor = BATCH * SEQ * HEADS * 64
    assert floor > 2048 * HEADS * 192
    moved = [
        (op, dtype, dims) for dtype, dims, op in re.findall(
            r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* (\w+)\(",
            _outside_fusions(block_text), re.M)
        if op in MOVES and dims
        and np.prod([int(d) for d in dims.split(",")]) >= floor]
    assert set(moved) <= ROTARY, moved
    assert len(moved) <= len(ROTARY), moved


def test_flash_attention_hands_its_kernels_a_head_a_row(one_chip,
                                                        no_persistent_cache):
    """A Mistral-shaped `flash_attention` (batch 4 x 4096, 32 heads of
    128), forward and backward: the kernels that the MLA kernels share
    read q, k, v, out and dO as [batch * heads, seq, 128], as before."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.core.device as core_device
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas import flash_attention as fa

    b, t, h = 4, 4096, 32

    def step(q, k, v):
        return jax.grad(lambda *a: jnp.sum(fa.flash_attention(
            *a, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)

    arg = jax.ShapeDtypeStruct((b, t, h, 128), jnp.bfloat16,
                               sharding=one_chip)
    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        text = jax.jit(step).trace(arg, arg, arg).lower(
            lowering_platforms=("tpu",)).compile().as_text()
    calls = dict(re.findall(
        r"%\w*?(flash_attention_(?:fwd|bwd_dq|bwd_dkv))[_.\d]* = .*? "
        r"custom-call\(.*?operand_layout_constraints=\{(.*?)\}, frontend_attr",
        text))
    assert set(calls) == set(fa.KERNELS)
    rows = f"bf16[{b * h},{t},128]{{2,1,0}}"
    for name, operands in calls.items():
        assert operands.startswith(f"{rows}, {rows}, {rows}"), (name,
                                                                 operands)
        assert f"[{b},{t},{h * 128}]" not in operands, (name, operands)
    # the backward kernels read dO (and lse and delta) a head a row too
    for name in fa.KERNELS[1:]:
        assert calls[name].startswith(f"{rows}, {rows}, {rows}, {rows}, "
                                      f"f32[{b * h},8,{t}]"), calls[name]
