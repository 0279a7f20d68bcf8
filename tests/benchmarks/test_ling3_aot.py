"""The kernels of `ling3flash.pretrain-8k` compiled ahead of time for a
described v5e at the cell's own shapes: the two KDA kernels at [2, 8192]
with 32 heads of 128 (eight heads a grid step). What Mosaic would refuse
on the chip fails here, at no chip time. The whole step (seven layers,
batch 2 x 8192, 1.6 GB of zero weights on the host, ~4 minutes) is marked
slow.

As tests/benchmarks/test_mellum2_aot.py: the topology is described inside
a fixture, and a moved internal that benchmarks/aot.py pins skips.
"""
import collections
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

V5E_HBM = 15.75 * 2**30
BATCH, SEQ, HEADS, D = 2, 8192, 32, 128


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
    """Kernel name of every KDA or MLA kernel call."""
    return re.findall(r"%\w*?((?:kda|mla_attention)_(?:fwd|bwd\w*?))"
                      r"[_.\d]* = ", compiled.as_text())


def test_kda_kernels_compile_at_the_cells_shape(one_chip,
                                                no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from benchmarks import aot
    from paddle_tpu.kernels.pallas import _compat
    from paddle_tpu.kernels.pallas import kda as K

    def step(q, k, v, g, beta):
        def loss(*a):
            o = K.kda(*a, num_heads=HEADS, impl="pallas")
            return jnp.sum(o.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    flat = lambda dtype: jax.ShapeDtypeStruct(
        (BATCH, SEQ, HEADS * D), dtype, sharding=one_chip)
    args = [flat(jnp.bfloat16)] * 3 + [flat(jnp.float32),
                                       jax.ShapeDtypeStruct(
                                           (BATCH, SEQ, HEADS), jnp.float32,
                                           sharding=one_chip)]
    try:
        patch = aot.as_on_tpu()
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    before = _compat.kda_blocks()
    with patch:
        compiled = jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    assert sorted(_calls(compiled)) == ["kda_bwd", "kda_fwd"]
    after = _compat.kda_blocks()
    for kernel in ("kda_fwd", "kda_bwd"):
        assert after[(kernel, 8, 4)] == before.get((kernel, 8, 4), 0) + 1


@pytest.mark.slow          # ~4 minutes alone, 1.6 GB of zero weights
def test_ling3_train_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from benchmarks import aot_ling3, run

    cfg = run.load(ROOT, "benchmarks", "configs",
                   "ling-3.0-flash-train1.json")
    traffic = run.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        lowered = aot_ling3.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip)
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    text = lowered.as_text(debug_info=True)
    for scope in ("kda", "attention", "moe", "mlp"):
        assert scope in text
    compiled = lowered.compile()
    # six KDA layers, recomputed: each forward twice and each backward
    # once; the MLA layer keeps its forward's output
    assert collections.Counter(_calls(compiled)) == {
        "kda_fwd": 12, "kda_bwd": 6, "mla_attention_fwd": 1,
        "mla_attention_bwd_dq": 1, "mla_attention_bwd_dkv": 1}
    m = compiled.memory_analysis()
    # the batch rule of the configuration: 2 rows where the compile
    # stays within 15.0 GiB
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < 15.0 * 2**30
