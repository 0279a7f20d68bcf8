"""The kernels of `granite4h.pretrain-8k` compiled ahead of time for a
described v5e at the cell's own shapes: the Mamba-2 scan forward and
backward at [2, 8192, 4096] (64 heads of 64, half a lane tile each, state
128) and flash attention at head size 64 over 8192 with the model's own
scale. What Mosaic would refuse on the chip fails here, at no chip time.
The whole step (ten layers, batch 2 x 8192, about a minute and 1.5 GB of
zero weights on the host) is marked slow.

As tests/benchmarks/test_qwen3_next_aot.py: the topology is described
inside a fixture, and a moved internal that benchmarks/aot.py pins skips.
"""
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

V5E_HBM = 15.75 * 2**30
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


def _compiled(fn, shapes, sharding):
    import jax

    from benchmarks import aot

    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    try:
        patch = aot.as_on_tpu()
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    with patch:
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()


def _kernels(compiled):
    return set(re.findall(
        r"%\w*?(mamba2_ssd_(?:fwd|bwd)|flash_attention_(?:fwd|bwd_dq"
        r"|bwd_dkv))[_.\d]* = ", compiled.as_text()))


def test_scan_kernels_compile_at_the_cells_shape(
        one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas import mamba2_ssd as S

    bf16, f32 = jnp.bfloat16, jnp.float32
    h, p, n = 64, 64, 128
    shapes = [((BATCH, SEQ, h * p), bf16), ((BATCH, SEQ, h), f32),
              ((h,), f32), ((BATCH, SEQ, n), bf16), ((BATCH, SEQ, n), bf16),
              ((h,), f32)]

    def step(*ops):
        def loss(*a):
            y = S.mamba2_ssd(*a, 256, impl="pallas")
            return jnp.sum(y.astype(f32)), y
        return jax.grad(loss, argnums=tuple(range(6)), has_aux=True)(*ops)

    compiled = _compiled(step, shapes, one_chip)
    assert {"mamba2_ssd_fwd", "mamba2_ssd_bwd"} <= _kernels(compiled)
    # the grid step the cell gets: 16 heads (eight pairs of half tiles)
    # of a chunk of 256
    assert S.choose_tile(SEQ, h, p, n, 1, 256, bf16) == (16, 256)
    # the chunk states kept for the backward (134 MB of float32) and the
    # cotangents; well inside what the step leaves free
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_flash_attention_compiles_at_head_dim_64_with_the_models_scale(
        one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    shape = ((BATCH, SEQ, 32, 64), jnp.bfloat16)

    def step(q, k, v):
        def loss(*a):
            o = flash_attention(*a, causal=True, scale=0.015625)
            return jnp.sum(o.astype(jnp.float32)), o
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    compiled = _compiled(step, [shape] * 3, one_chip)
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= _kernels(compiled)


@pytest.mark.slow          # 70 s alone, 1.5 GB of zero weights on the host
def test_granite_train_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from benchmarks import aot_granite_hybrid, run

    cfg = run.load(ROOT, "benchmarks", "configs",
                   "granite-4.0-h-micro-train1.json")
    traffic = run.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        lowered = aot_granite_hybrid.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip)
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    # the device scope of the Mamba mixer is in the lowered step
    assert "state_space" in lowered.as_text(debug_info=True)
    compiled = lowered.compile()
    assert {"mamba2_ssd_fwd", "mamba2_ssd_bwd", "flash_attention_fwd",
            "flash_attention_bwd_dq", "flash_attention_bwd_dkv"} <= _kernels(
                compiled)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM
