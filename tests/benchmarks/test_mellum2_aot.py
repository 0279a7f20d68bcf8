"""The kernels of `mellum2.pretrain-8k` compiled ahead of time for a
described v5e at the cell's own shapes: the three band-schedule kernels at
[2, 8192] with 32 heads of 128 and a window of 1024. What Mosaic would
refuse on the chip fails here, at no chip time. The whole step (four
layers, batch 2 x 8192, 1.2 GB of zero weights on the host) is marked
slow.

As tests/benchmarks/test_kanana2_aot.py: the topology is described inside
a fixture, and a moved internal that benchmarks/aot.py pins skips.
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
BATCH, SEQ, HEADS, WINDOW = 2, 8192, 32, 1024


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


def _calls(compiled):
    """Instruction name -> kernel name of every attention kernel call."""
    return re.findall(
        r"%(\w*?(flash_attention_(?:window_)?(?:fwd|bwd_dq|bwd_dkv))"
        r"[_.\d]*) = ", compiled.as_text())


def test_window_kernels_compile_at_the_cells_shape(one_chip,
                                                   no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from benchmarks import aot
    from paddle_tpu.kernels.pallas import flash_attention as fa

    def step(q, k, v):
        def loss(*a):
            o = fa.flash_attention(*a, window=WINDOW)
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    args = [jax.ShapeDtypeStruct((BATCH, SEQ, HEADS, 128), jnp.bfloat16,
                                 sharding=one_chip)] * 3
    try:
        patch = aot.as_on_tpu()
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    with patch:
        compiled = jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    kernels = [k for _, k in _calls(compiled)]
    assert sorted(kernels) == sorted(fa.WINDOW_KERNELS)
    for kernel in fa.WINDOW_KERNELS:
        assert fa.choose_window_blocks(SEQ, WINDOW, 128, jnp.bfloat16,
                                       kernel) == (1024, 1024)
    assert fa.band_steps(SEQ, WINDOW, 1024, 1024) == (2, 2)


@pytest.mark.slow          # a minute or two alone, 1.2 GB of zero weights
def test_mellum2_train_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from benchmarks import aot_mellum2, run
    from paddle_tpu.kernels.pallas import flash_attention as fa

    cfg = run.load(ROOT, "benchmarks", "configs",
                   "mellum2-12b-a2.5b-train1.json")
    traffic = run.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        lowered = aot_mellum2.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip)
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    text = lowered.as_text(debug_info=True)
    for scope in ("sliding_attention", "full_attention", "moe"):
        assert scope in text
    compiled = lowered.compile()
    kernels = [k for _, k in _calls(compiled)]
    # three sliding layers and one full, each forward kernel once (the
    # recomputed layer keeps its output), each backward kernel once
    for kernel in fa.WINDOW_KERNELS:
        assert kernels.count(kernel) == 3, kernel
    for kernel in fa.KERNELS:
        assert kernels.count(kernel) == 1, kernel
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM
