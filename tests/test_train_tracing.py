"""The tracing of the training path: spans on the profiler's clock, device
scopes that the backward re-enters, named kernels, and the host spans of
TrainStep, the loader and the compile (docs/observability.md).
"""
import glob
import re
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import autograd
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import jit_events, span, spans

# what benchmarks/kernels/flash_attention.py takes for the kernel
KERNEL_TEXT = re.compile(r"tpu_custom_call|pallas|flash")
SCOPES = ("embedding", "attention", "mlp", "lm_head_loss", "optimizer")


@pytest.fixture
def ring():
    spans.clear_finished_spans()
    yield spans
    spans.set_span_buffer_capacity(4096)
    spans.clear_finished_spans()


def _children(ring, parent):
    return [s.name for s in ring.finished_spans()
            if s.parent_id == parent.span_id]


# ------------------------------------------------------------ the mechanism
def test_span_is_an_event_of_a_plain_jax_profiler_session(ring, tmp_path):
    """Nobody told paddle_tpu.profiler about this session; the span is in
    its xplane all the same, over the interval the ring holds."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe.outer", step=7):
            time.sleep(0.01)
            with span("probe.inner"):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = ProfileData.from_file(path)
    origin = next(int(v) for plane in data.planes for k, v in plane.stats
                  if k == "profile_start_time")
    events = {ev.name: ev for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name.startswith("probe.")}
    assert set(events) == {"probe.outer", "probe.inner"}
    for sp in ring.finished_spans():
        ev = events[sp.name]
        start = origin + int(ev.start_ns)
        # 100 us on the chip's host (PERF.md); a loaded test box may
        # preempt between the annotation and the clock read
        assert abs(sp.start_ns - start) < 2e6
        assert abs(sp.end_ns - (start + int(ev.duration_ns))) < 2e6
        assert isinstance(sp.start_ns, int) and sp.end_ns >= sp.start_ns


def test_last_gives_the_newest_n_and_none_once_the_ring_has_wrapped(ring):
    ring.set_span_buffer_capacity(8)
    for i in range(5):
        with span("a", i=i):
            pass
    assert [s.attrs["i"] for s in ring.last("a", 3)] == [2, 3, 4]
    assert ring.last("a", 5) is not None and ring.last("a", 6) is None
    assert ring.last("never", 1) is None
    for i in range(6):
        with span("b"):
            pass
    # eight places hold six b and the two newest a: three a are gone
    assert [s.attrs["i"] for s in ring.last("a", 2)] == [3, 4]
    assert ring.last("a", 3) is None


def test_recorded_span_keeps_the_times_it_was_given(ring):
    with span("parent") as parent:
        sp = ring.record("jit.compile", 5_000, 9_000, cache_hit=True)
    assert (sp.start_ns, sp.end_ns, sp.parent_id) == (
        5_000, 9_000, parent.span_id)
    assert sp.duration_s == pytest.approx(4e-6)
    assert ring.last("jit.compile", 1) == [sp]


# ------------------------------------------------------------ device scopes
def _tiny_step(accum_steps=1, **config):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(**config))
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: m(ids, labels=ids)[1], opt,
        accum_steps=accum_steps)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, 128, (2, 32)).astype(np.int32))
    return step, ids


def _op_names(step, ids):
    """The op_name of every operation of the step's lowered module. The
    body of the accumulation's scan names its operations from the scope
    or the transform on: those are handed out under ``body/``."""
    operands = step._prepare((ids,), {})     # builds step._compiled
    lowered = step._compiled.lower(*operands)
    names = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))
    in_body = re.compile(r"(%s)/|(jvp|transpose)\(" % "|".join(SCOPES))
    return ({n for n in names if n.startswith("jit(staged)/")}
            | {"body/" + n for n in names if in_body.match(n)})


@pytest.mark.parametrize("accum_steps", [1, 2])
@pytest.mark.parametrize("fused_loss_chunk", [0, 16])
def test_train_step_lowers_with_forward_and_backward_under_their_scope(
        fused_loss_chunk, accum_steps):
    """Whatever ``accum_steps``, the module is ``jit(staged)`` (the name
    every ``PROGRAMS`` pattern under benchmarks/kernels/ finds the step
    by) and the update is one ``optimizer`` scope at its top level."""
    step, ids = _tiny_step(accum_steps, fused_loss_chunk=fused_loss_chunk)
    with jit_events.suppress():
        names = _op_names(step, ids)

    def under(scope, marker):
        return [n for n in names if f"/{scope}/" in n and marker in n]

    for scope in ("attention", "mlp", "lm_head_loss"):
        assert under(scope, "/jvp("), scope
        assert under(scope, "/transpose(jvp("), scope
    assert under("optimizer", "")
    assert all(n.startswith("jit(staged)/optimizer/")
               for n in under("optimizer", ""))
    # the tape runs every vjp outside the scopes; call_vjp re-enters them
    scoped = re.compile("/(" + "|".join(SCOPES) + ")/")
    assert not [n for n in names
                if "transpose(jvp(" in n and not scoped.search(n)]
    # at this size attention is XLA's: nothing may look like a kernel
    assert not [n for n in names if KERNEL_TEXT.search(n)]


def test_only_the_kernel_calls_match_the_kernel_pattern():
    """With the flash kernel on the path (lowered for the TPU, Mosaic and
    all), the scope names still keep clear of the pattern by which
    benchmarks/kernels/flash_attention.py finds the kernel's time."""
    from paddle_tpu.core import device as core_device
    from paddle_tpu.kernels.pallas import _compat

    paddle.set_flags({"FLAGS_flash_attention_min_seq": 128})
    try:
        step, _ = _tiny_step(hidden_size=256, num_attention_heads=2,
                             max_position_embeddings=256)
        ids = paddle.to_tensor(np.zeros((2, 128), np.int32))
        operands = step._prepare((ids,), {})
        with mock.patch.object(core_device, "on_tpu", lambda: True), \
                mock.patch.object(_compat, "on_tpu", lambda: True), \
                jit_events.suppress():
            text = step._compiled.trace(*operands).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    finally:
        paddle.set_flags({"FLAGS_flash_attention_min_seq": 2048})
    names = {n for n in re.findall(r'loc\("([^"]+)"', text)
             if n.startswith("jit(staged)/")}
    hits = {n for n in names if KERNEL_TEXT.search(n)}
    assert hits and all(n.endswith("/pallas_call") for n in hits)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dq",
                   "flash_attention_bwd_dkv"):
        assert [n for n in hits if f"({kernel})" in n and "/attention/" in n]
        assert f'kernel_name = "{kernel}"' in text


def test_scope_in_eager_mode_changes_no_result_and_the_tape_frees():
    rng = np.random.RandomState(0)
    xs = rng.randn(4, 8).astype(np.float32)
    ws = rng.randn(8, 8).astype(np.float32)

    def run(scoped):
        x = paddle.to_tensor(xs, stop_gradient=False)
        w = paddle.to_tensor(ws, stop_gradient=False)
        if scoped:
            with autograd.scope("attention"):
                h = paddle.tanh(paddle.matmul(x, w))
                with autograd.scope("inner"):
                    y = (h * h).sum()
        else:
            h = paddle.tanh(paddle.matmul(x, w))
            y = (h * h).sum()
        node, mid = y._grad_node, h._grad_node
        y.backward()
        return y.numpy(), x.grad.numpy(), w.grad.numpy(), node, mid

    plain, scoped = run(False), run(True)
    for a, b in zip(plain[:3], scoped[:3]):
        np.testing.assert_array_equal(a, b)
    assert plain[3].scope == () and scoped[4].scope == ("attention",)
    assert scoped[3].scope == ("attention", "inner")
    assert autograd._state.scope == ()
    for node in scoped[3:]:
        assert node.vjp_fn is None          # freed by backward()


# ------------------------------------------------------------ named kernels
def test_pl_call_requires_a_name():
    from paddle_tpu.kernels.pallas._compat import pl_call

    with pytest.raises(TypeError, match="name"):
        pl_call(lambda x_ref, o_ref: None,
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))


def _flash(fn):
    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    return fn, (q, q, q)


def _flash_fwd():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    return _flash(lambda q, k, v: flash_attention(q, k, v))


def _flash_bwd():
    from paddle_tpu.kernels.pallas.flash_attention import flash_attention

    return _flash(jax.grad(
        lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))


def _paged():
    from paddle_tpu.kernels.pallas.paged_attention import paged_attention

    pool = jnp.zeros((2, 16, 16, 128), jnp.bfloat16)
    return paged_attention, (
        jnp.zeros((4, 4, 128), jnp.bfloat16), pool, pool,
        jnp.zeros((4, 4), jnp.int32), jnp.full((4,), 20, jnp.int32))


def _grouped():
    from paddle_tpu.kernels.pallas.grouped_matmul import grouped_matmul

    return (lambda a, b, g: grouped_matmul(a, b, g, impl="pallas")), (
        jnp.zeros((256, 128), jnp.bfloat16),
        jnp.zeros((4, 128, 256), jnp.bfloat16),
        jnp.full((4,), 64, jnp.int32))


@pytest.mark.parametrize("build,kernels", [
    (_flash_fwd, ["flash_attention_fwd"]),
    (_flash_bwd, ["flash_attention_bwd_dq", "flash_attention_bwd_dkv"]),
    (_paged, ["paged_attention_decode"]),
    (_grouped, ["grouped_matmul"]),
])
def test_kernel_lowered_for_the_tpu_holds_its_name(build, kernels):
    """The name reaches the Mosaic custom call (`kernel_name`) and the
    operation's path, from which the TPU's compiler names the instruction
    that a device trace shows."""
    from paddle_tpu.core import device as core_device
    from paddle_tpu.kernels.pallas import _compat

    fn, args = build()
    with mock.patch.object(core_device, "on_tpu", lambda: True), \
            mock.patch.object(_compat, "on_tpu", lambda: True):
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in text
    for kernel in kernels:
        assert f'kernel_name = "{kernel}"' in text
        assert re.search(rf'loc\("[^"]*[(/]{kernel}\)*/pallas_call"', text)


# --------------------------------------------------------------- host spans
def test_train_step_call_is_one_span_with_three_children(ring):
    step, ids = _tiny_step()
    for expected in (1, 2):
        ring.clear_finished_spans()
        step(ids)
        (top,) = ring.last("train_step", 1)
        assert top.attrs == {"step": expected} and top.parent_id is None
        assert _children(ring, top)[-3:] == [
            "train_step.prepare", "train_step.launch", "train_step.rebind"]
        parts = [ring.last("train_step." + p, 1)[0]
                 for p in ("prepare", "launch", "rebind")]
        assert top.start_ns <= parts[0].start_ns
        assert all(a.end_ns <= b.start_ns for a, b in zip(parts, parts[1:]))
        assert parts[-1].end_ns <= top.end_ns


class _Rows(paddle.io.Dataset):
    def __len__(self):
        return 12

    def __getitem__(self, i):
        return np.full((4,), i, np.int32)


@pytest.mark.parametrize("transport,loader_args", [
    ("process", dict(num_workers=2, use_shared_memory=True)),
    ("sync", dict(num_workers=0)),
    ("thread", dict(num_workers=2)),
])
def test_every_next_of_the_loader_is_a_span_with_its_parts(
        ring, transport, loader_args):
    loader = paddle.io.DataLoader(_Rows(), batch_size=3, **loader_args)
    batches = [b.numpy() for b in loader]
    assert [int(b[0, 0]) for b in batches] == [0, 3, 6, 9]
    tops = ring.last("loader.next", 5)[:4] if transport != "process" \
        else ring.last("loader.next", 4)
    assert [t.attrs["batch"] for t in tops] == [0, 1, 2, 3]
    for top in tops:
        kids = _children(ring, top)
        if transport == "thread":
            # worker threads collate and place ahead of the consumer
            assert kids == ["loader.wait"]
        else:
            assert kids == ["loader.wait", "loader.unpack", "loader.h2d"]
    waits = ring.last("loader.wait", 4)
    assert all(isinstance(w.attrs["ready"], int) for w in waits)
    assert len([s for s in ring.finished_spans()
                if s.name == "loader.h2d"]) == 4


def test_compile_phases_are_spans_under_a_watch_and_only_there(ring):
    """Under a watch the phases are the watch's (``kind="train_step"``,
    children of the launch); a compile on a thread with no watch open is
    filed too, once, as ``kind="unwatched"`` under JAX's own name."""
    jit_events.clear_compile_log()
    step, ids = _tiny_step()
    step(ids)
    (event,) = [e for e in jit_events.compile_log()
                if e["kind"] == "train_step"]
    launch = ring.last("train_step.launch", 1)[0]
    total = 0.0
    for phase in ("trace", "lower", "compile"):
        found = [s for s in ring.finished_spans()
                 if s.name == "jit." + phase
                 and s.attrs["kind"] == "train_step"]
        assert found and all(s.parent_id == launch.span_id for s in found)
        seconds = sum(s.duration_s for s in found)
        assert event[phase + "_s"] == pytest.approx(seconds, abs=1e-6)
        assert all(launch.start_ns - 5e6 <= s.start_ns
                   and s.end_ns <= launch.end_ns + 5e6 for s in found)
        total += seconds
    assert "cache_hit" in ring.last("jit.compile", 1)[0].attrs
    # only outermost intervals are kept: the phases fit inside the call,
    # and nothing else was heard while the launch was open
    assert 0 < total <= event["elapsed_s"] + 5e-3
    assert not [s for s in ring.finished_spans()
                if s.name.startswith("jit.") and s.parent_id == launch.span_id
                and s.attrs["kind"] != "train_step"]
    # the warm call compiles nothing; a bare jit files one unwatched set
    ring.clear_finished_spans()
    step(ids)
    assert not [s for s in ring.finished_spans()
                if s.name.startswith("jit.")]
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5))
    bare = [s for s in ring.finished_spans() if s.name.startswith("jit.")
            and "<lambda>" in s.attrs["fn"]]
    assert [(s.name, s.attrs["fn"]) for s in bare] == [
        ("jit.trace", "<lambda>"), ("jit.lower", "jit(<lambda>)"),
        ("jit.compile", "jit(<lambda>)")]
    assert all(s.attrs["kind"] == "unwatched" and s.parent_id is None
               for s in bare)
    assert not [e for e in jit_events.compile_log()
                if e["kind"] != "train_step"]
