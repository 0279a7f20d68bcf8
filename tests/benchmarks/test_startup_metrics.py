"""The ten `startup.*` metrics (benchmarks/reducers/span_self_time.py): the
self time of the program's set-up spans, over a hand-made ring whose
nesting is known, and over the ring a tiny run leaves on the CPU.
"""
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R            # noqa: E402
from benchmarks import run as run_mod         # noqa: E402
from benchmarks.reducers import span_self_time as S   # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELLS = ["mistral7b.pretrain-4k", "qwen3next.pretrain-8k",
         "granite4h.pretrain-8k"]
# the hand-worked self times of `_time_line` below, seconds
EXPECTED = {
    "startup.before_program_s": 2.0,
    "startup.import_s": 1.5,
    "startup.loader_s": 1.6,
    "startup.optimizer_state_s": 1.8,
    "startup.step_build_s": 1.0,
    "startup.step_trace_s": 3.0,
    "startup.step_compile_s": 2.0,
    "startup.step_host_s": 2.3,
    "startup.other_jit_s": 1.7,
    "startup.unnamed_s": 3.1,
}
SETUP_S = 20.0
BASE = 1_700_000_000 * 10**9        # a wall clock's nanoseconds


def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _readings(**counts):
    return R.Readings({}, counts, {"chips": 1, "config": {}}, {})


def _value(metric, readings):
    return R.reduce_metric(_spec(metric), readings)


@pytest.fixture
def ring():
    from paddle_tpu.observability import spans

    spans.clear_finished_spans()
    yield spans
    spans.set_span_buffer_capacity(4096)
    spans.clear_finished_spans()


def _at(ring, name, start_s, end_s, **attrs):
    return ring.record(name, BASE + round(start_s * 1e9),
                       BASE + round(end_s * 1e9), **attrs)


def _jit(ring, kind, trace, lower, compile_, **attrs):
    _at(ring, "jit.trace", *trace, kind=kind, **attrs)
    _at(ring, "jit.lower", *lower, kind=kind, **attrs)
    _at(ring, "jit.compile", *compile_, kind=kind, cache_hit=False, **attrs)


def _warm_step(ring, i, start, wait, step):
    """A loader.next of `wait` seconds whose one child waits for half of
    it, then a train_step of `step` seconds whose three children leave it
    0.1 s of its own."""
    _at(ring, "loader.next", start, start + wait, batch=i)
    _at(ring, "loader.wait", start, start + wait / 2, ready=0)
    t = start + wait
    _at(ring, "train_step", t, t + step, step=i + 1)
    _at(ring, "train_step.prepare", t, t + 0.1)
    _at(ring, "train_step.launch", t + 0.1, t + step - 0.2)
    _at(ring, "train_step.rebind", t + step - 0.2, t + step - 0.1)


def _time_line(ring, window_steps=3, import_=True):
    """Set-up ends 18 s after `runtime.import` opened and took 20 s: two
    seconds of it came before the program.

      0.0- 1.5  runtime.import
      2.0- 2.5  the caller's weights: one unwatched compile (0.1, 0.1, 0.3)
      2.6- 3.0  loader.start
      3.0- 4.0  loader.next: wait 0.7, unpack 0.1, h2d 0.15, own 0.05
      4.0-14.0  train_step 1
        4.0- 8.0  prepare: build 1.0, init_state 2.5 (0.7 of it an
                  unwatched compile), own 0.5
        8.0-13.5  launch: the step's trace 2.0, lower 1.0, compile 2.0;
                  own 0.5
       13.5-13.9  rebind; train_step's own 0.1
     14.0-15.0  nothing of the main thread's that is read: a span of a
                worker thread, a serving compile, a span of another name
     15.0-16.0  loader.next 0.2 (wait 0.1) and train_step 2 (0.8)
     16.5-17.0  the caller's readings: one unwatched compile
     17.95-18.2 a compile that ends after set-up's end: not read
     18.0-      the window's steps; the reference's compile at 30 s
    """
    if import_:
        _at(ring, "runtime.import", 0.0, 1.5)
    _jit(ring, "unwatched", (2.0, 2.1), (2.1, 2.2), (2.2, 2.5), fn="weights")
    _at(ring, "loader.start", 2.6, 3.0, workers=2)
    _at(ring, "loader.next", 3.0, 4.0, batch=0)
    _at(ring, "loader.wait", 3.0, 3.7, ready=0)
    _at(ring, "loader.unpack", 3.7, 3.8)
    _at(ring, "loader.h2d", 3.8, 3.95)
    _at(ring, "train_step", 4.0, 14.0, step=1)
    _at(ring, "train_step.prepare", 4.0, 8.0)
    _at(ring, "train_step.build", 4.0, 5.0)
    _at(ring, "optimizer.init_state", 5.0, 7.5, leaves=9, bytes=72)
    _jit(ring, "unwatched", (5.1, 5.2), (5.2, 5.3), (5.3, 5.8), fn="zeros")
    _at(ring, "train_step.launch", 8.0, 13.5)
    _at(ring, "train_step.rebind", 13.5, 13.9)
    # filed when the watch closes: after the launch they are children of
    _jit(ring, "train_step", (8.1, 10.1), (10.1, 11.1), (11.1, 13.1),
         fn="loss_fn")
    worker = threading.Thread(
        target=_at, args=(ring, "loader.unpack", 14.0, 14.9))
    worker.start()
    worker.join()
    _at(ring, "jit.trace", 14.2, 14.4, kind="serving", fn="decode")
    _at(ring, "serving.decode", 14.5, 14.6)
    _warm_step(ring, 1, 15.0, 0.2, 0.8)
    _at(ring, "jit.compile", 16.5, 17.0, kind="unwatched", fn="readings",
        cache_hit=True)
    _at(ring, "jit.trace", 17.95, 18.2, kind="unwatched", fn="late")
    for i in range(window_steps):
        _warm_step(ring, 2 + i, 18.0 + i, 0.1, 0.5)
    _jit(ring, "unwatched", (30.0, 30.5), (30.5, 30.6), (30.6, 31.0),
         fn="reference")
    return _readings(steps=window_steps, setup_s=SETUP_S)


# ------------------------------------------------- files and their entries
@pytest.mark.parametrize("metric", list(EXPECTED))
def test_startup_metric_has_its_entry_its_file_and_its_hand_worked_value(
        metric, ring):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == metric]
    assert entry == {
        "name": metric, "unit": "s", "better": "lower",
        "source": "program_span", "layer": "set-up", "moves": "setup_s",
        "workloads": CELLS}
    spec = _spec(metric)
    assert set(spec) == {"reducer", "args", "reads"}
    assert spec["reducer"] == "span_self_time"
    # an untraced run, an empty ring: left out of the line, never raised
    assert R.reduce_metric(spec, _readings(steps=64, setup_s=30.0)) is None
    r = _time_line(ring)
    assert _value(metric, r) == pytest.approx(EXPECTED[metric], abs=1e-9)


def test_the_ten_are_the_manifests_last_and_name_no_other_cell():
    assert [m["name"] for m in MANIFEST["per_layer"][-10:]] == list(EXPECTED)
    assert not [m for m in MANIFEST["per_layer"][:-10]
                if m["name"].startswith("startup.") or m["layer"] == "set-up"]
    kanana = next(w for w in MANIFEST["workloads"]
                  if w["name"] == "kanana2.pretrain-8k")
    assert not [m["name"] for m in run_mod.cell_metrics(
        MANIFEST, kanana, "per_layer") if m["name"].startswith("startup.")]


def test_the_ten_add_up_to_setup_s(ring):
    r = _time_line(ring)
    values = {m: _value(m, r) for m in EXPECTED}
    assert sum(values.values()) == pytest.approx(SETUP_S, abs=1e-9)
    assert sum(EXPECTED.values()) == pytest.approx(SETUP_S, abs=1e-9)
    # and on a clock that did not keep to tenths of a second
    ring.clear_finished_spans()
    r = _time_line(ring)
    r.counts["setup_s"] = 19.123456789
    values = {m: _value(m, r) for m in EXPECTED}
    assert sum(values.values()) == pytest.approx(19.123456789, abs=1e-9)
    assert values["startup.before_program_s"] == pytest.approx(
        1.123456789, abs=1e-9)


def test_the_files_read_each_span_of_the_time_line_once():
    """What the eight named metrics select is `READ`, no name twice:
    `_unnamed_` is what is left, so the ten partition set-up."""
    picked = []
    for metric in EXPECTED:
        args = _spec(metric)["args"]
        kind = args.get("where", {}).get("kind")
        picked += [(name, kind) for name in args["names"]
                   if name not in (S.BEFORE, S.UNNAMED)]
    assert len(picked) == len(set(picked))
    wanted = [(name, kind) for name, kinds in S.READ.items()
              for kind in (kinds or (None,))]
    assert sorted(picked, key=str) == sorted(wanted, key=str)


def test_where_and_names_select_like_program_span(ring):
    r = _time_line(ring)
    assert S.read(r, ["jit.compile"]) == pytest.approx(0.3 + 0.5 + 2.0 + 0.5)
    assert S.read(r, ["jit.compile"], where={"fn": "zeros"}) == (
        pytest.approx(0.5))
    assert S.read(r, ["jit.compile"], where={"cache_hit": True}) == (
        pytest.approx(0.5))
    assert S.read(r, ["serving.decode"]) == 0.0       # not of the time line
    with pytest.raises(ValueError):
        S.read(r, ["jit.compile"], part="window")


# ----------------------------------------------------- nothing to read
@pytest.mark.parametrize("case", [
    "no_import", "wrapped", "no_steps", "no_setup_s", "fewer_steps_held",
    "setup_shorter_than_the_ring", "no_reader"])
def test_a_ring_that_cannot_give_the_whole_time_line_gives_none(
        case, ring, monkeypatch):
    if case == "wrapped":
        ring.set_span_buffer_capacity(40)   # the oldest span goes first
    r = _time_line(ring, import_=case != "no_import")
    if case == "no_steps":
        r.counts["steps"] = 0
    elif case == "no_setup_s":
        del r.counts["setup_s"]
    elif case == "fewer_steps_held":
        r.counts["steps"] = 7
    elif case == "setup_shorter_than_the_ring":
        r.counts["setup_s"] = 17.0          # the import came before it
    elif case == "no_reader":
        monkeypatch.delattr(ring, "last")
    for metric in EXPECTED:
        assert _value(metric, r) is None, metric


def test_without_a_loader_the_first_train_step_ends_setup(ring):
    _at(ring, "runtime.import", 0.0, 1.0)
    _at(ring, "train_step", 2.0, 3.0, step=1)
    for i in range(2):
        _at(ring, "train_step", 4.0 + i, 4.5 + i, step=2 + i)
    r = _readings(steps=2, setup_s=4.5)
    assert _value("startup.before_program_s", r) == pytest.approx(0.5)
    assert _value("startup.step_host_s", r) == pytest.approx(1.0)
    assert _value("startup.unnamed_s", r) == pytest.approx(2.0)
    assert _value("startup.loader_s", r) == 0.0


# ------------------------------------------------- a tiny run, on the CPU
def test_a_tiny_run_files_every_span_the_reducer_reads(
        ring, tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    cfg = run_mod.load(ROOT, "benchmarks", "configs",
                       "mistral-7b-v0.3-train1.json")
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256,
               num_hidden_layers=2, max_position_embeddings=256,
               initializer_range=0.1)
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    cfg["limits"] = {"loss1_gap": 5e-4, "loss3_gap": 5e-4,
                     "grad1_worst_leaf_gap": 0.02,
                     "change_worst_leaf_gap": 0.02}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-4k.json")
    traffic.update(seq_len=64, rows=4096)
    cell = {"name": "tiny.pretrain", "config": "tiny",
            "traffic": "pretrain-4k", "chips": 1}
    manifest = {"end_to_end": [{"name": "setup_s", "unit": "s"}],
                "per_layer": [{"name": m, "unit": "s"} for m in EXPECTED]}
    # this process imported the program long ago; the span is put back
    # where this run's set-up can hold it
    ring.record("runtime.import", time.time_ns() - 10**6, time.time_ns())
    line, run = run_mod.run_cell(manifest, cell, cfg, traffic, 2**31 + 7,
                                 1.0, 1, require_chip=False)
    assert line["correct"], line["checks"]
    assert line["metrics"] == {}          # off the chip: no device metric
    found = {(s.name, s.attrs.get("kind") if s.name.startswith("jit.")
              else None) for s in ring.finished_spans()}
    wanted = {(name, kind) for name, kinds in S.READ.items()
              for kind in (kinds or (None,))}
    assert wanted <= found
    r = _readings(**run.counts)
    values = {m: _value(m, r) for m in EXPECTED}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert sum(values.values()) == pytest.approx(
        run.counts["setup_s"], abs=1e-6)
    for metric in ("startup.optimizer_state_s", "startup.step_build_s",
                   "startup.step_trace_s", "startup.step_compile_s",
                   "startup.step_host_s", "startup.loader_s",
                   "startup.other_jit_s"):
        assert values[metric] > 0, metric
    # the window filed no start-up span, and PR 25's readers still find
    # their steps: the ring holds set-up and the window together
    first = ring.last("loader.next", run.counts["steps"])[0]
    late = [s for s in ring.finished_spans() if s.start_ns >= first.start_ns
            and s.end_ns <= ring.last("train_step", 1)[0].end_ns]
    assert not [s.name for s in late if s.name in (
        "optimizer.init_state", "train_step.build", "loader.start")
        or s.attrs.get("kind") == "unwatched"]
    assert _value("train_step.host_ms", r) > 0
