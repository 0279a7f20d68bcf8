"""A start-up's time line in the span ring: the package's import, the
optimizer's state, the step's build, the loader's start and every compile
heard outside a watch, each once and on the thread it ran on
(docs/observability.md, "Reading a start-up").
"""
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import jit_events, span, spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ONCE = ("optimizer.init_state", "train_step.build", "loader.start")

# what a user's script does, in a process of its own: the ring starts empty
SCRIPT = r"""
import json, sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import spans


class Rows(paddle.io.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.full((32,), i, np.int32)


paddle.seed(0)
model = LlamaForCausalLM(LlamaConfig.tiny())
opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
step = paddle.jit.TrainStep(model, lambda m, ids: m(ids, labels=ids)[1], opt)
feed = iter(paddle.io.DataLoader(Rows(), batch_size=2, num_workers=2,
                                 use_shared_memory=True))
for _ in range(4):
    step(next(feed))
spans.export_chrome_trace(sys.argv[1])
print(json.dumps({
    "leaves": len(step._params),
    "state_bytes": sum(a.nbytes for st in opt._accumulators.values()
                       for a in st.values()),
    "spans": [{"name": s.name, "attrs": s.attrs, "id": s.span_id,
               "parent": s.parent_id, "tid": s.tid, "start": s.start_ns,
               "end": s.end_ns} for s in spans.finished_spans()]}))
"""


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """The ring of a new process that built a TrainStep over a forked
    loader and ran four steps, and its Chrome-trace export."""
    path = tmp_path_factory.mktemp("startup") / "trace.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    out = subprocess.run([sys.executable, "-c", SCRIPT, str(path)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    told = json.loads(out.stdout.strip().splitlines()[-1])
    told["events"] = [json.loads(line) for line in open(path)]
    return told


def _named(told, name):
    return [s for s in told["spans"] if s["name"] == name]


def test_the_import_is_the_rings_first_span(fresh):
    (imported,) = _named(fresh, "runtime.import")
    assert fresh["spans"][0] is imported and imported["parent"] is None
    # JAX came with it: the caller had not imported it
    assert imported["end"] - imported["start"] > 50e6
    assert all(s["start"] >= imported["end"] for s in fresh["spans"][1:])


def test_the_first_step_makes_state_and_builds_and_later_steps_do_not(fresh):
    steps = _named(fresh, "train_step")
    assert [s["attrs"]["step"] for s in steps] == [1, 2, 3, 4]
    (prepare,) = [s for s in _named(fresh, "train_step.prepare")
                  if s["parent"] == steps[0]["id"]]
    (state,), (build,) = (_named(fresh, "optimizer.init_state"),
                          _named(fresh, "train_step.build"))
    assert state["parent"] == build["parent"] == prepare["id"]
    assert build["end"] <= state["start"]
    assert state["attrs"] == {"leaves": fresh["leaves"],
                              "bytes": fresh["state_bytes"]}
    assert fresh["leaves"] > 0 and fresh["state_bytes"] > 0
    # the state's small compiles are its children, under JAX's own names
    made = [s for s in fresh["spans"] if s["parent"] == state["id"]]
    assert made and all(s["name"].startswith("jit.")
                        and s["attrs"]["kind"] == "unwatched"
                        and s["attrs"]["fn"] for s in made)
    assert all(state["start"] <= s["start"] and s["end"] <= state["end"]
               for s in made)


def test_the_loader_starts_once_and_outside_every_next(fresh):
    (start,) = _named(fresh, "loader.start")
    assert start["attrs"] == {"workers": 2} and start["parent"] is None
    nexts = _named(fresh, "loader.next")
    assert len(nexts) == 4 and start["end"] <= nexts[0]["start"]


def test_steps_two_to_four_file_no_startup_span(fresh):
    second = _named(fresh, "loader.next")[1]["start"]
    late = [s for s in fresh["spans"] if s["start"] >= second]
    assert {s["name"] for s in late} == {
        "loader.next", "loader.wait", "loader.unpack", "loader.h2d",
        "train_step", "train_step.prepare", "train_step.launch",
        "train_step.rebind"}
    # and the one watched compile is the train step's, filed once
    for phase in ("trace", "lower", "compile"):
        (watched,) = [s for s in _named(fresh, "jit." + phase)
                      if s["attrs"]["kind"] == "train_step"]
        launch = next(s for s in fresh["spans"]
                      if s["id"] == watched["parent"])
        assert launch["name"] == "train_step.launch"
        assert not [s for s in _named(fresh, "jit." + phase)
                    if s["attrs"]["kind"] == "unwatched"
                    and s["parent"] == launch["id"]]


def test_every_span_and_its_exported_event_carry_the_spans_thread(fresh):
    (imported,) = _named(fresh, "runtime.import")
    assert all(s["tid"] == imported["tid"] for s in fresh["spans"])
    assert len(fresh["events"]) == len(fresh["spans"])
    for sp, ev in zip(fresh["spans"], fresh["events"]):
        assert ev["name"] == sp["name"]
        assert ev["tid"] == sp["tid"] & 0x7FFFFFFF


# ------------------------------------------------------- in this process
@pytest.fixture
def ring():
    spans.clear_finished_spans()
    yield spans
    spans.set_span_buffer_capacity(4096)
    spans.clear_finished_spans()


def _jit_spans(ring):
    return [s for s in ring.finished_spans() if s.name.startswith("jit.")]


def test_an_unwatched_compile_is_three_spans_whatever_it_nests(ring):
    inner = jax.jit(lambda x: jnp.tanh(x) + 1)

    def outer_probe(x):            # traces `inner` inside its own trace
        return inner(x) * inner(x + 1)

    x = jnp.ones((3, 11))
    ring.clear_finished_spans()
    with span("around") as around:
        jax.jit(outer_probe)(x)
    found = _jit_spans(ring)
    assert [(s.name, s.attrs["fn"]) for s in found] == [
        ("jit.trace", "outer_probe"), ("jit.lower", "jit(outer_probe)"),
        ("jit.compile", "jit(outer_probe)")]
    assert all(s.attrs["kind"] == "unwatched"
               and s.parent_id == around.span_id for s in found)
    assert found[2].attrs["cache_hit"] in (False, True)
    assert all(a.end_ns <= b.start_ns for a, b in zip(found, found[1:]))
    assert around.start_ns <= found[0].start_ns
    assert found[2].end_ns <= around.end_ns
    # compiled: the next call is heard by nobody
    ring.clear_finished_spans()
    jax.jit(outer_probe)(x)
    assert not _jit_spans(ring)


def _watched_probe(x):
    jit_events.mark_traced()
    return x * 5 - 2


def test_a_watched_compile_is_filed_once_under_the_watchs_kind(ring):
    x = jnp.ones((3, 13))
    ring.clear_finished_spans()
    with jit_events.watch("probe", kind="probe"):
        jax.jit(_watched_probe)(x)
    found = _jit_spans(ring)
    assert [s.name for s in found] == ["jit.trace", "jit.lower",
                                      "jit.compile"]
    assert all(s.attrs["kind"] == "probe" and s.attrs["fn"] == "probe"
               for s in found)


def test_suppress_masks_watched_and_unwatched_compiles(ring):
    x = jnp.ones((3, 17))
    ring.clear_finished_spans()
    with jit_events.suppress():
        jax.jit(lambda x: x * 7)(x)
        with jit_events.watch("masked", kind="probe"):
            jax.jit(lambda x: _watched_probe(x) + 1)(x)
    assert not _jit_spans(ring)
    # and the count of JAX's open intervals came out even: the next
    # compile is an outermost one again
    jax.jit(lambda x: x * 9)(x)
    assert len(_jit_spans(ring)) == 3


def test_a_span_holds_the_thread_it_ran_on_not_the_exporters(ring):
    told = {}

    def work():
        with span("worker.side") as sp:
            told["entered"] = sp.tid
        told["recorded"] = ring.record("worker.given", 5, 9).tid

    t = threading.Thread(target=work)
    t.start()
    t.join()
    with span("main.side"):
        pass
    by_name = {s.name: s for s in ring.finished_spans()}
    assert told == {"entered": t.ident, "recorded": t.ident}
    assert by_name["main.side"].tid == threading.get_ident() != t.ident
    assert by_name["worker.side"].to_chrome_event()["tid"] == (
        t.ident & 0x7FFFFFFF)
    assert by_name["main.side"].to_chrome_event()["tid"] == (
        threading.get_ident() & 0x7FFFFFFF)


class _Rows(paddle.io.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, i):
        return np.full((32,), i % 7, np.int32)


def _count(ring):
    found = ring.finished_spans()
    counts = {name: sum(s.name == name for s in found) for name in ONCE}
    counts["unwatched"] = sum(
        s.attrs.get("kind") == "unwatched" for s in found)
    return counts


@pytest.mark.parametrize("workers,loader_args", [
    (2, dict(num_workers=2, use_shared_memory=True)),
    (0, dict(num_workers=0)),
    (2, dict(num_workers=2)),
], ids=["process", "sync", "thread"])
def test_a_window_files_no_startup_span(ring, workers, loader_args):
    """Each iterator starts once; after the first step the ring's count of
    the start-up spans stands still."""
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny())
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m(ids, labels=ids)[1], opt)
    feed = iter(paddle.io.DataLoader(_Rows(), batch_size=2, **loader_args))
    ring.clear_finished_spans()
    counts = []
    for _ in range(4):
        step(next(feed))
        counts.append(_count(ring))
    assert {k: counts[0][k] for k in ONCE} == dict.fromkeys(ONCE, 1)
    assert counts[1:] == [counts[0]] * 3
    (start,) = [s for s in ring.finished_spans() if s.name == "loader.start"]
    assert start.attrs == {"workers": workers}
    assert start.parent_id is None
    # a second epoch is a second iterator
    next(iter(paddle.io.DataLoader(_Rows(), batch_size=2, **loader_args)))
    assert _count(ring)["loader.start"] == 2
