"""The kernels of `kanana2.pretrain-8k` compiled ahead of time for a
described v5e at the cell's own shapes: the three MLA kernels at [2, 8192]
with 32 heads of 128 + 64 under values of 128 and one shared rotary key.
What Mosaic would refuse on the chip fails here, at no chip time. The
whole step (six layers, batch 2 x 8192, a minute and a half and 1.4 GB of
zero weights on the host) is marked slow.

As tests/benchmarks/test_granite_hybrid_aot.py: the topology is described
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
BATCH, SEQ, HEADS = 2, 8192, 32


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


def _kernels(compiled):
    return set(re.findall(
        r"%\w*?(mla_attention_(?:fwd|bwd_dq|bwd_dkv)|grouped_matmul"
        r"(?:_dlhs|_drhs)?)[_.\d]* = ", compiled.as_text()))


def test_mla_kernels_compile_at_the_cells_shape(one_chip,
                                                no_persistent_cache):
    import jax
    import jax.numpy as jnp

    from benchmarks import aot
    from paddle_tpu.kernels.pallas import flash_attention as fa

    bf16 = jnp.bfloat16
    shapes = [(BATCH, SEQ, HEADS, 128), (BATCH, SEQ, HEADS, 64),
              (BATCH, SEQ, HEADS, 128), (BATCH, SEQ, 1, 64),
              (BATCH, SEQ, HEADS, 128)]

    def step(*ops):
        def loss(*a):
            o = fa.mla_attention(*a, scale=192 ** -0.5, impl="pallas")
            return jnp.sum(o.astype(jnp.float32)), o
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*ops)

    args = [jax.ShapeDtypeStruct(s, bf16, sharding=one_chip) for s in shapes]
    try:
        patch = aot.as_on_tpu()
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    with patch:
        compiled = jax.jit(step).trace(*args).lower(
            lowering_platforms=("tpu",)).compile()
    assert set(fa.MLA_KERNELS) <= _kernels(compiled)
    text = compiled.as_text()
    # the shared rotary key enters every kernel as [batch, seq, 64], one
    # head, and its cotangent leaves `dk`/`dv` already summed
    calls = re.findall(r"%\w*mla_attention_\w+[.\d]* = (.*?) custom-call\("
                       r".*?operand_layout_constraints=\{(.*?)\}, frontend_attr",
                       text)
    assert len(calls) == 3
    for results, operands in calls:
        assert operands.count(f"bf16[{BATCH},{SEQ},64]") == 1, operands
        assert operands.count(f"bf16[{BATCH * HEADS},{SEQ},64]") == 1
        assert ",256]" not in operands and ",192]" not in operands
    dkv = next(r for r, o in calls if r.count("bf16[") == 3)
    assert f"bf16[{BATCH},{SEQ},64]" in dkv
    # the tiles the cell gets, chosen at the score's whole width
    for kernel in fa.KERNELS:
        assert fa.choose_blocks(SEQ, SEQ, 192, bf16, kernel) == (1024, 1024)


@pytest.mark.slow          # 120 s alone, 1.4 GB of zero weights on the host
def test_kanana2_train_step_compiles_for_v5e(one_chip, no_persistent_cache):
    from benchmarks import aot_deepseek_v3, run

    cfg = run.load(ROOT, "benchmarks", "configs",
                   "kanana-2-30b-a3b-train1.json")
    traffic = run.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    try:
        lowered = aot_deepseek_v3.lower_train_step(
            cfg, cfg["train"]["batch_per_replica"], traffic["seq_len"],
            one_chip)
    except AttributeError as e:
        pytest.skip(f"an internal that benchmarks/aot.py pins has moved: {e}")
    assert "attention.core" in lowered.as_text(debug_info=True)
    compiled = lowered.compile()
    assert {"mla_attention_fwd", "mla_attention_bwd_dq",
            "mla_attention_bwd_dkv", "grouped_matmul", "grouped_matmul_dlhs",
            "grouped_matmul_drhs"} <= _kernels(compiled)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM
