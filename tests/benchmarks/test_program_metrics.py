"""The per-layer metrics that read what the program's tracing emits: the
named flash kernels in a device trace, and the spans of the program's own
ring (benchmarks/reducers/program_span.py, scope_time_share.py and the
four flash_attention_* files under benchmarks/kernels/), over a synthetic
trace and ring with hand-worked values.
"""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R            # noqa: E402
from benchmarks import run as run_mod         # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "mistral7b.pretrain-4k"
MISTRAL = {"hidden_size": 4096, "intermediate_size": 14336,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "head_dim": 128, "vocab_size": 32768, "num_hidden_layers": 2}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["kernels.flash_attention_fwd_roofline",
       "kernels.flash_attention_bwd_roofline",
       "kernels.flash_attention_fwd.time_share",
       "kernels.flash_attention_bwd_dq.time_share",
       "kernels.flash_attention_bwd_dkv.time_share",
       "train_step.host_ms", "train_step.host_ms_max", "loader.wait_ms",
       "loader.deliver_ms", "loader.ready_batches", "setup.jit_trace_s",
       "setup.jit_compile_s"]
ms = 1e-3


def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": MISTRAL}, PEAKS,
                      trace=trace)


def _call(name, operands, target="tpu_custom_call"):
    """An instruction as the TPU's trace shows it: its whole text."""
    return (f"%{name} = bf16[128,4096,128]{{2,1,0}} custom-call("
            f"{operands}), custom_call_target=\"{target}\"")


def _step_trace(kernels):
    """One step of 20 ms, busy throughout: forward kernel 3 ms, dq 2 ms,
    dkv 2.5 ms, and two bystanders that consume a kernel's result and so
    name it among their operands (0.25 ms each), as on the chip."""
    fwd, dq, dkv = kernels
    ops = [
        (0 * ms, 9 * ms, "%fusion.104 = bf16[4,4096,4096] fusion("
         "bf16[4,4096,4096] %param.1), kind=kOutput", ""),
        (9 * ms, 12 * ms, _call(fwd + ".1", "%bitcast.4, %bitcast.9"), ""),
        (12 * ms, 12.25 * ms, "%multiply_reduce_fusion = f32[128,4096] "
         "fusion(bf16[128,4096,128] %pallas_call.8), kind=kLoop", ""),
        (12.25 * ms, 14.25 * ms, _call(dq + ".1", "%bitcast.4"), ""),
        (14.25 * ms, 16.75 * ms, _call(dkv + ".1", "%bitcast.4"), ""),
        (16.75 * ms, 17 * ms, f"%convert.43 = f32[128,4096,128] convert("
         f"bf16[128,4096,128] %{dq}.1)", ""),
        (17 * ms, 20 * ms, "%convert_select_fusion.5 = bf16[4096,14336] "
         "fusion(bf16[4096] %copy-done.9), kind=kOutput", ""),
    ]
    lines = {0: {"ops": ops, "modules": [(0.0, 20 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


CHANGE = ("jvp_flash_attention_fwd_", "jvp_flash_attention_bwd_dq_",
          "jvp_flash_attention_bwd_dkv_")
PARENT = ("jvp__", "transpose_jvp___", "transpose_jvp___")


def _value(metric, readings):
    return R.reduce_metric(_spec(metric), readings)


# ------------------------------------------------------------ named kernels
def test_the_three_kernels_are_told_apart_by_their_own_names():
    r = _readings(_step_trace(CHANGE), flash_sequences=4,
                  flash_seq_len=4096)
    fwd = _value("kernels.flash_attention_fwd.time_share", r)
    dq = _value("kernels.flash_attention_bwd_dq.time_share", r)
    dkv = _value("kernels.flash_attention_bwd_dkv.time_share", r)
    assert (fwd, dq, dkv) == pytest.approx((15.0, 10.0, 12.5))
    # the accepted file's pattern is not anchored at the instruction's own
    # name: it takes the kernels and whatever names one as an operand
    old = _value("kernels.flash_attention.time_share", r)
    assert old == pytest.approx(fwd + dq + dkv + 2 * 1.25)


def test_forward_and_backward_rooflines_split_the_required_work():
    from benchmarks.kernels import flash_attention, flash_attention_bwd
    from benchmarks.kernels import flash_attention_bwd_dq as dq_alone
    from benchmarks.kernels import flash_attention_fwd

    counts = {"flash_sequences": 4, "flash_seq_len": 4096}
    cell = {"config": MISTRAL}
    whole = flash_attention.least_seconds(counts, cell, PEAKS)
    fwd = flash_attention_fwd.least_seconds(counts, cell, PEAKS)
    bwd = flash_attention_bwd.least_seconds(counts, cell, PEAKS)
    assert fwd == pytest.approx(whole / 3)
    assert bwd == pytest.approx(2 * whole / 3)
    assert dq_alone.least_seconds(counts, cell, PEAKS) is None
    assert flash_attention_fwd.least_seconds({}, cell, PEAKS) is None
    r = _readings(_step_trace(CHANGE), **counts)
    assert _value("kernels.flash_attention_fwd_roofline", r) == (
        pytest.approx(100 * fwd / 3e-3))
    assert _value("kernels.flash_attention_bwd_roofline", r) == (
        pytest.approx(100 * bwd / 4.5e-3))


def test_a_program_without_the_names_reads_nothing():
    """The parent commit's kernels are called after the transformation
    they were traced under; the new files find nothing and say so."""
    r = _readings(_step_trace(PARENT), flash_sequences=4,
                  flash_seq_len=4096)
    for metric in NEW[:5]:
        assert _value(metric, r) is None
    assert _value("kernels.flash_attention.time_share", r) is not None


# ----------------------------------------------------------- program spans
@pytest.fixture
def ring():
    from paddle_tpu.observability import spans

    spans.clear_finished_spans()
    yield spans
    spans.set_span_buffer_capacity(4096)
    spans.clear_finished_spans()


def _fill(ring, steps, first=0):
    """Steps whose spans have hand-set times: train_step lasts 2 + i ms,
    loader.wait i ms with ready=i, unpack 1 ms, h2d 0.5 ms."""
    for i in range(first, first + steps):
        t = i * 10**9
        ring.record("loader.wait", t, t + i * 10**6, ready=i)
        ring.record("loader.unpack", t, t + 10**6)
        ring.record("loader.h2d", t, t + 5 * 10**5)
        ring.record("train_step", t, t + (2 + i) * 10**6, step=i)


def test_span_metrics_read_exactly_the_windows_steps(ring):
    _fill(ring, 4)                # set-up: four warm-up steps
    _fill(ring, 3, first=4)       # the window: steps 4, 5, 6
    r = _readings(steps=3)
    assert _value("train_step.host_ms", r) == pytest.approx(7.0)
    assert _value("train_step.host_ms_max", r) == pytest.approx(8.0)
    assert _value("loader.wait_ms", r) == pytest.approx(5.0)
    assert _value("loader.deliver_ms", r) == pytest.approx(1.5)
    assert _value("loader.ready_batches", r) == pytest.approx(5.0)


def test_span_metrics_read_none_once_the_ring_has_wrapped(ring):
    ring.set_span_buffer_capacity(10)
    _fill(ring, 3)                # twelve spans into ten places:
    r = _readings(steps=3)        # the first wait and unpack are gone
    assert _value("train_step.host_ms", r) == pytest.approx(3.0)
    for metric in ("loader.wait_ms", "loader.deliver_ms",
                   "loader.ready_batches"):
        assert _value(metric, r) is None
    assert _value("train_step.host_ms", _readings()) is None   # no count


def test_compile_metrics_sum_the_train_steps_phases_alone(ring):
    for kind, scale in (("train_step", 1), ("serving", 100)):
        ring.record("jit.trace", 0, 2 * scale * 10**9, kind=kind)
        ring.record("jit.lower", 0, 1 * scale * 10**9, kind=kind)
        ring.record("jit.compile", 0, 40 * scale * 10**9, kind=kind,
                    cache_hit=False)
    ring.record("jit.compile", 0, 5 * 10**8, kind="train_step",
                cache_hit=True)
    r = _readings(steps=3)
    assert _value("setup.jit_trace_s", r) == pytest.approx(3.0)
    assert _value("setup.jit_compile_s", r) == pytest.approx(40.5)
    ring.clear_finished_spans()
    assert _value("setup.jit_compile_s", r) is None


def test_a_program_without_the_rings_reader_reads_nothing(ring, monkeypatch):
    _fill(ring, 3)
    monkeypatch.delattr(ring, "last")
    r = _readings(steps=3)
    for metric in NEW[5:]:
        assert _value(metric, r) is None


# ------------------------------------------------------------ scope shares
def _scoped_trace(paths):
    ops = [(i * ms, (i + d) * ms, f"%fusion.{i} = bf16[8] fusion("
            "bf16[8] %attention_mask.1)", path)
           for i, (d, path) in enumerate(paths)]
    return R.summarize_events({0: {"ops": ops, "modules": [
        (0.0, len(paths) * ms, "jit_staged")]}}, [], set())


def test_scope_shares_add_to_a_hundred_and_the_first_scope_wins():
    from benchmarks.reducers import scope_time_share as S

    t = _scoped_trace([
        (1.0, "jit(staged)/attention/jvp()/dot_general"),
        (1.0, "jit(staged)/attention/transpose(jvp())/dot_general"),
        (0.5, "jit(staged)/mlp/jvp()/dot_general"),
        (0.5, "jit(staged)/lm_head_loss/jvp()/while"),
        # the update of the attention weights: the optimizer's, not theirs
        (0.5, "jit(staged)/optimizer/jit(step_fn)/attention/mul"),
        (0.25, "jit(staged)/embedding/jvp(jit(_take))/gather"),
        (0.25, "jit(staged)/add"),
    ])
    r = _readings(t)
    shares = {s: S.read(r, s) for s in S.ORDER + (S.UNSCOPED,)}
    assert shares == pytest.approx({
        "attention": 50.0, "mlp": 12.5, "lm_head_loss": 12.5,
        "optimizer": 12.5, "unscoped": 12.5})
    assert sum(shares.values()) == pytest.approx(100.0)


def test_scope_share_is_not_read_from_instruction_names_alone():
    """What reduce.read_xplane hands over today: the instruction's text,
    operands and all, and no path. No share is made up from that."""
    from benchmarks.reducers import scope_time_share as S

    r = _readings(_scoped_trace([(1.0, ""), (1.0, "")]))
    assert all(S.read(r, s) is None for s in S.ORDER + (S.UNSCOPED,))
    assert S.read(_readings(), "attention") is None


# ------------------------------------------------- files and their entries
@pytest.mark.parametrize("metric", NEW)
def test_new_metric_has_its_entry_its_file_and_reads_nothing_from_nothing(
        metric, ring):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == ("setup_s" if metric.startswith("setup.")
                              else "train_tokens_per_s_per_chip")
    spec = _spec(metric)
    assert set(spec) == {"reducer", "args", "reads"}
    # an untraced run, an empty ring: left out of the line, never raised
    assert R.reduce_metric(spec, _readings(steps=64)) is None
