"""The kernels of `qwen3next.pretrain-8k` compiled ahead of time for a
described v5e at the cell's own shapes: the gated delta rule forward and
backward, the three grouped-matmul kernels at 32 experts of 2048 x 512, and
flash attention at head_dim 256 over 8192. What Mosaic would refuse on the
chip fails here, at no chip time. The whole step (four layers, batch 4 x
8192, about two minutes) is marked slow.

As tests/benchmarks/test_benchmark_aot.py: the topology is described inside
a fixture, and a moved internal that benchmarks/aot.py pins skips.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

V5E_HBM = 15.75 * 2**30
BATCH, SEQ = 4, 8192


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
    import re

    return set(re.findall(
        r"%\w*?(gated_delta_rule_(?:fwd|bwd)|grouped_matmul(?:_dlhs|_drhs)?"
        r"|flash_attention_(?:fwd|bwd_dq|bwd_dkv))[_.\d]* = ",
        compiled.as_text()))


def test_delta_rule_kernels_compile_at_the_cells_shape(
        one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas.gated_delta_rule import gated_delta_rule

    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = [((BATCH, SEQ, 16, 128), bf16), ((BATCH, SEQ, 16, 128), bf16),
              ((BATCH, SEQ, 32, 128), bf16), ((BATCH, SEQ, 32), f32),
              ((BATCH, SEQ, 32), f32)]

    def step(q, k, v, g, beta):
        def loss(*a):
            o = gated_delta_rule(*a, impl="pallas")
            return jnp.sum(o.astype(f32)), o
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            q, k, v, g, beta)

    compiled = _compiled(step, shapes, one_chip)
    assert {"gated_delta_rule_fwd", "gated_delta_rule_bwd"} <= _kernels(
        compiled)
    # the chunk states kept for the backward: 1 GiB of float32, and the
    # cotangents; well inside what the step leaves free
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30


@pytest.mark.parametrize("k,m", [(2048, 512), (512, 2048)])
def test_grouped_matmul_kernels_compile_at_the_cells_shape(
        one_chip, no_persistent_cache, k, m):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul

    rows = 40960           # MoELayer.held_rows(32768) at 32 of 512, k 10
    shapes = [((rows, k), jnp.bfloat16), ((32, k, m), jnp.bfloat16),
              ((32,), jnp.int32)]

    def step(lhs, rhs, sizes):
        def loss(a, b):
            out = grouped_matmul(a, b, sizes, impl="pallas")
            return jnp.sum(out.astype(jnp.float32)), out
        return jax.grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)

    compiled = _compiled(step, shapes, one_chip)
    assert {"grouped_matmul", "grouped_matmul_dlhs",
            "grouped_matmul_drhs"} <= _kernels(compiled)


def test_flash_attention_compiles_at_head_dim_256_over_8192(
        one_chip, no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    shape = ((BATCH, SEQ, 16, 256), jnp.bfloat16)

    def step(q, k, v):
        def loss(*a):
            o = flash_attention(*a, causal=True)
            return jnp.sum(o.astype(jnp.float32)), o
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    compiled = _compiled(step, [shape] * 3, one_chip)
    assert {"flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= _kernels(compiled)


@pytest.mark.slow          # 110 s alone
def test_hybrid_train_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from benchmarks import aot_hybrid, run

    cfg = run.load(ROOT, "benchmarks", "configs",
                   "qwen3-next-80b-a3b-train1.json")
    traffic = run.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        lowered = aot_hybrid.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip)
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    compiled = lowered.compile()
    assert {"gated_delta_rule_fwd", "gated_delta_rule_bwd", "grouped_matmul",
            "grouped_matmul_dlhs", "grouped_matmul_drhs",
            "flash_attention_fwd", "flash_attention_bwd_dq",
            "flash_attention_bwd_dkv"} <= _kernels(compiled)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM
