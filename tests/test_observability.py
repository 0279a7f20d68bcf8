"""paddle_tpu.observability: metrics registry, spans, flight recorder.

Compile-lean by design (tier-1 budget): the only XLA programs built
here are one tiny to_static function and the module-scope tiny-Llama
serving engine (prefill + decode, shared across the serving tests).
Everything else is host-side.
"""
import gc
import json
import os
import signal
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed.watchdog import CommTimeoutError, CommWatchdog
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import jit_events
from paddle_tpu.observability.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from paddle_tpu.resilience import FaultSpec, faults
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(scope="module")
def engine(model):
    return Engine(model, EngineConfig(
        max_batch_slots=2, max_model_len=32, page_size=8,
    ))


@pytest.fixture
def flight_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLIGHT_DIR", str(tmp_path))
    return tmp_path


class TestMetricsRegistry:
    def test_counter_gauge_histogram(self):
        reg = MetricsRegistry()
        c = reg.counter("paddle_tpu_t_total", "c", ("site",))
        c.inc(site="a")
        c.inc(2, site="a")
        assert c.labels(site="a").value == 3
        with pytest.raises(ValueError):
            c.labels(site="a").inc(-1)
        g = reg.gauge("paddle_tpu_t_gauge", "g")
        g.set(2.5)
        g.dec()
        assert g.value == 1.5
        h = reg.histogram("paddle_tpu_t_s", "h", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(7)
        assert h.count == 3 and h.sum == pytest.approx(7.55)

    def test_get_or_create_idempotent_and_conflicts(self):
        reg = MetricsRegistry()
        a = reg.counter("paddle_tpu_x_total", "h", ("k",))
        assert reg.counter("paddle_tpu_x_total", "h", ("k",)) is a
        with pytest.raises(ValueError):
            reg.gauge("paddle_tpu_x_total")        # kind conflict
        with pytest.raises(ValueError):
            reg.counter("paddle_tpu_x_total", "h", ("other",))
        with pytest.raises(ValueError):
            reg.counter("bad name")
        with pytest.raises(ValueError):
            reg.counter("ok_name", labelnames=("bad-label",))
        h = reg.histogram("paddle_tpu_h_s", buckets=(0.1, 1.0))
        assert reg.histogram("paddle_tpu_h_s", buckets=(1.0, 0.1)) is h
        with pytest.raises(ValueError, match="different buckets"):
            reg.histogram("paddle_tpu_h_s", buckets=(10, 60))

    def test_prometheus_exposition_golden(self):
        """Exact text exposition — the scrape contract."""
        reg = MetricsRegistry()
        c = reg.counter(
            "paddle_tpu_requests_total", "requests", ("code",)
        )
        c.inc(3, code="200")
        c.inc(code="503")
        reg.gauge("paddle_tpu_queue_depth", "depth").set(4)
        h = reg.histogram(
            "paddle_tpu_step_seconds", "steps", buckets=(0.1, 1.0)
        )
        h.observe(0.05)
        h.observe(0.2)
        assert reg.render_prometheus() == (
            "# HELP paddle_tpu_queue_depth depth\n"
            "# TYPE paddle_tpu_queue_depth gauge\n"
            "paddle_tpu_queue_depth 4\n"
            "# HELP paddle_tpu_requests_total requests\n"
            "# TYPE paddle_tpu_requests_total counter\n"
            'paddle_tpu_requests_total{code="200"} 3\n'
            'paddle_tpu_requests_total{code="503"} 1\n'
            "# HELP paddle_tpu_step_seconds steps\n"
            "# TYPE paddle_tpu_step_seconds histogram\n"
            'paddle_tpu_step_seconds_bucket{le="0.1"} 1\n'
            'paddle_tpu_step_seconds_bucket{le="1"} 2\n'
            'paddle_tpu_step_seconds_bucket{le="+Inf"} 2\n'
            "paddle_tpu_step_seconds_sum 0.25\n"
            "paddle_tpu_step_seconds_count 2\n"
        )

    def test_snapshot_and_collector_view(self):
        reg = MetricsRegistry()
        reg.gauge("paddle_tpu_g").set(1)

        alive = [True]

        def collect():
            if not alive[0]:
                return None
            return [obs.MetricFamily("paddle_tpu_view", "gauge").add(
                7, {"engine": "e1"}
            )]

        reg.register_collector("view", collect)
        snap = reg.snapshot()
        assert snap["paddle_tpu_g"] == 1
        assert snap["paddle_tpu_view{engine=e1}"] == 7
        alive[0] = False        # dead view unregisters itself
        assert "paddle_tpu_view{engine=e1}" not in reg.snapshot()
        assert reg.snapshot() == reg.snapshot()

    def test_same_name_families_merge_into_one_type_stanza(self):
        """Two engines export the same series names under different
        labels; the exposition must carry ONE # TYPE per name or
        Prometheus rejects the whole scrape."""
        reg = MetricsRegistry()
        for eid in ("e1", "e2"):
            def collect(eid=eid):
                return [obs.MetricFamily(
                    "paddle_tpu_serving_x_total", "counter", "x",
                ).add(1, {"engine": eid})]

            reg.register_collector(f"view.{eid}", collect)
        text = reg.render_prometheus()
        assert text.count("# TYPE paddle_tpu_serving_x_total") == 1
        assert 'engine="e1"' in text and 'engine="e2"' in text

    def test_raising_collector_is_skipped_not_fatal(self, capsys):
        reg = MetricsRegistry()
        reg.gauge("paddle_tpu_ok").set(1)
        calls = [0]

        def broken():
            calls[0] += 1
            raise AttributeError("mid-construction")

        reg.register_collector("broken", broken)
        text = reg.render_prometheus()
        assert "paddle_tpu_ok 1" in text
        assert "skipped this scrape" in capsys.readouterr().err
        # kept registered: a transient failure recovers next scrape
        reg.render_prometheus()
        assert calls[0] == 2

    def test_escaping_and_registry_register(self):
        reg = MetricsRegistry()
        c = Counter("paddle_tpu_esc_total", "e", ("msg",))
        reg.register(c)
        c.inc(msg='say "hi"\nnow')
        text = reg.render_prometheus()
        assert r'msg="say \"hi\"\nnow"' in text
        with pytest.raises(ValueError):
            reg.register(Counter("paddle_tpu_esc_total"))
        assert isinstance(Gauge("g"), Gauge)
        assert isinstance(Histogram("h"), Histogram)


class TestSpans:
    def test_nesting_and_ids(self):
        obs.spans.clear_finished_spans()
        assert obs.current_span() is None
        assert obs.current_traceparent() is None
        with obs.span("outer") as s1:
            assert obs.current_span() is s1
            with obs.span("inner", step=3) as s2:
                assert s2.trace_id == s1.trace_id
                assert s2.parent_id == s1.span_id
                assert s2.attrs == {"step": 3}
        assert obs.current_span() is None
        done = obs.finished_spans()
        assert [s.name for s in done] == ["inner", "outer"]
        assert done[0].duration_s is not None

    def test_remote_span_binding(self):
        with obs.span("client") as s1:
            tp = obs.current_traceparent()
        assert tp == f"{s1.trace_id}-{s1.span_id}"
        with obs.remote_span("server", tp) as srv:
            assert srv.trace_id == s1.trace_id
            assert srv.parent_id == s1.span_id
            assert obs.current_trace_id() == s1.trace_id
        # None / garbage degrade to no-op
        with obs.remote_span("server", None):
            assert obs.current_span() is None
        with obs.remote_span("server", "garbage"):
            assert obs.current_span() is None

    def test_chrome_trace_jsonl_export(self, tmp_path):
        obs.spans.clear_finished_spans()
        with obs.span("a"):
            with obs.span("b"):
                pass
        path = str(tmp_path / "trace.jsonl")
        assert obs.export_chrome_trace(path) == path
        lines = [json.loads(ln) for ln in open(path)]
        assert len(lines) == 2
        for ev in lines:
            assert ev["ph"] == "X" and ev["pid"] == os.getpid()
            assert {"ts", "dur", "name"} <= set(ev)
        by_name = {ev["name"]: ev for ev in lines}
        assert (by_name["b"]["args"]["parent_id"]
                == by_name["a"]["args"]["span_id"])

    def test_export_degrades_on_fault(self, tmp_path):
        spec = FaultSpec(OSError("disk"), at=1)
        with faults.inject({"obs.export": spec}) as inj:
            with pytest.warns(UserWarning, match="degraded"):
                out = obs.export_chrome_trace(str(tmp_path / "t.jsonl"))
        assert out is None and inj.fired["obs.export"] == 1


class TestTracePropagation:
    def test_store_rpc_carries_trace_context(self):
        from paddle_tpu.distributed.store import TCPStore

        store = TCPStore("127.0.0.1", 29743, is_master=True, timeout=10)
        try:
            obs.spans.clear_finished_spans()
            with obs.span("client-op") as sp:
                store.set("obs/k", "v")
                assert store.get("obs/k") == "v"
            # the server sends each response INSIDE its remote_span
            # (the span finishes — and lands in the ring — after the
            # client already has the reply), so the last op's span can
            # trail the client by a scheduler quantum: poll briefly
            # instead of racing the handler thread
            deadline = time.time() + 5.0
            while True:
                names = {
                    s.name: s for s in obs.finished_spans()
                    if s.name.startswith("store.")
                }
                if ({"store.set", "store.get"} <= set(names)
                        or time.time() >= deadline):
                    break
                time.sleep(0.01)
            assert {"store.set", "store.get"} <= set(names)
            for s in names.values():
                assert s.trace_id == sp.trace_id
                assert s.parent_id == sp.span_id
            # untraced traffic creates no server spans
            obs.spans.clear_finished_spans()
            store.set("obs/k2", "v")
            assert not [
                s for s in obs.finished_spans()
                if s.name.startswith("store.")
            ]
        finally:
            store.close()

    def test_rpc_round_trip_propagates(self):
        """Live distributed.rpc round trip: the remote handler observes
        the caller's trace id (satellite acceptance)."""
        from paddle_tpu.distributed import rpc

        rpc.init_rpc(
            "obs0", rank=0, world_size=1,
            master_endpoint="127.0.0.1:29745",
        )
        try:
            with obs.span("request") as sp:
                assert rpc.rpc_sync("obs0", _remote_trace_id) == sp.trace_id
                fut = rpc.rpc_async("obs0", _remote_trace_id)
                assert fut.wait() == sp.trace_id
            # no open span -> the handler sees none either
            assert rpc.rpc_sync("obs0", _remote_trace_id) is None
        finally:
            rpc.shutdown()


def _remote_trace_id():
    return obs.current_trace_id()


class TestCompileLog:
    def test_to_static_compiles_once_then_silent(self):
        jit_events.clear_compile_log()

        @paddle.jit.to_static
        def tiny(x):
            return x * 2 + 1

        x = paddle.to_tensor(np.ones((2, 2), "float32"))
        tiny(x)
        log1 = [e for e in jit_events.compile_log()
                if e["fn"] == "tiny"]
        assert len(log1) == 1
        ev = log1[0]
        assert ev["kind"] == "to_static" and not ev["retrace"]
        assert ev["elapsed_s"] and ev["elapsed_s"] > 0
        tiny(x)   # warm: no new event
        assert len([e for e in jit_events.compile_log()
                    if e["fn"] == "tiny"]) == 1
        # new shape = a fresh compile, NOT a retrace
        tiny(paddle.to_tensor(np.ones((3, 2), "float32")))
        log3 = [e for e in jit_events.compile_log() if e["fn"] == "tiny"]
        assert len(log3) == 2 and not log3[-1]["retrace"]

    def test_retrace_after_warmup_is_alarmable(self):
        before = jit_events.retraces_after_warmup("unit")
        with jit_events.watch("f", kind="unit", signature="s0"):
            jit_events.mark_traced()
        assert jit_events.retraces_after_warmup("unit") == before
        with jit_events.watch("f", kind="unit", signature="s0"):
            jit_events.mark_traced()   # same (fn, signature): alarm
        assert jit_events.retraces_after_warmup("unit") == before + 1
        assert jit_events.compile_log()[-1]["retrace"]

    def test_suppress_masks_analysis_traces(self):
        n0 = len(jit_events.compile_log())
        with jit_events.suppress():
            with jit_events.watch("g", kind="unit", signature="x"):
                jit_events.mark_traced()
        assert len(jit_events.compile_log()) == n0

    def test_unwatched_trace_still_logged(self):
        jit_events.mark_traced("orphan", kind="unit", signature="q")
        ev = jit_events.compile_log()[-1]
        assert ev["fn"] == "orphan" and ev["elapsed_s"] is None


class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = obs.FlightRecorder(capacity=8)
        for i in range(20):
            rec.record("t", f"e{i}")
        evs = rec.events()
        assert len(evs) == 8 and evs[0]["name"] == "e12"

    def test_dump_contents_and_cli(self, flight_dir):
        obs.record("test", "marker", detail=1)
        path = obs.dump("unit-test", probes={"p": {"status": "ok"}})
        assert path and os.path.exists(path)
        payload = json.load(open(path))
        assert payload["reason"] == "unit-test"
        assert payload["probes"] == {"p": {"status": "ok"}}
        assert any(
            e["name"] == "marker" for e in payload["events"]
        )
        assert "compile_log" in payload and "metrics" in payload
        assert obs.find_dumps(str(flight_dir))[0] == path
        from paddle_tpu.observability.__main__ import main

        assert main(["dump", path]) == 0
        assert main(["dump"]) == 0
        assert main(["dump", "--list"]) == 0
        assert main(["metrics"]) == 0

    def test_dump_degrades_on_export_fault(self, flight_dir):
        spec = FaultSpec(OSError("disk full"), at=1)
        with faults.inject({"obs.export": spec}) as inj:
            with pytest.warns(UserWarning, match="degraded"):
                assert obs.dump("faulted") is None
        assert inj.fired["obs.export"] == 1
        assert obs.find_dumps(str(flight_dir)) == []

    @pytest.mark.skipif(
        not hasattr(signal, "SIGUSR2"), reason="no SIGUSR2"
    )
    def test_sigusr2_dumps(self, flight_dir):
        assert obs.install_signal_handler()
        os.kill(os.getpid(), signal.SIGUSR2)
        deadline = time.time() + 5
        while not obs.find_dumps(str(flight_dir)):
            assert time.time() < deadline
            time.sleep(0.01)
        payload = json.load(open(obs.find_dumps(str(flight_dir))[0]))
        assert payload["reason"] == "sigusr2"


class TestWatchdogIntegration:
    def test_forced_trip_dumps_flight_recorder(self, flight_dir, engine):
        """Acceptance: a forced watchdog trip produces a postmortem
        containing the compile log, the last fault fires, and the
        engine health snapshot."""
        # make sure a compile and a fault fire precede the trip
        engine.generate([[1, 2, 3]], SamplingParams(max_new_tokens=2))
        with faults.inject(
            {"serving.step": FaultSpec(RuntimeError("boom"), at=1)}
        ):
            engine.generate([[4, 5]], SamplingParams(max_new_tokens=2))
        wd = CommWatchdog(
            timeout=0.3, poll_interval=0.05, on_timeout=lambda t, w: None,
        )
        probe_name = f"serving.engine.{engine.engine_id}"
        wd.register_probe(probe_name, engine.health, owner=engine)
        try:
            with pytest.raises(CommTimeoutError):
                with wd.watch("forced-hang"):
                    time.sleep(0.8)
        finally:
            wd.shutdown()
        dumps = obs.find_dumps(str(flight_dir))
        assert dumps, "watchdog trip wrote no postmortem"
        payload = json.load(open(dumps[0]))
        assert payload["reason"].startswith("watchdog-trip")
        health = payload["probes"][probe_name]
        assert health["status"] in ("ok", "degraded", "overloaded")
        assert any(
            e["kind"] == "serving" for e in payload["compile_log"]
        )
        assert any(
            e["category"] == "fault" and e["name"] == "serving.step"
            for e in payload["events"]
        )
        assert any(
            e["category"] == "watchdog" and e["name"] == "trip"
            for e in payload["events"]
        )

    def test_unregister_and_dead_owner_prune(self):
        wd = CommWatchdog(timeout=5, on_timeout=lambda t, w: None)
        try:
            wd.register_probe("keep", lambda: {})
            wd.register_probe("drop", lambda: {})
            assert wd.unregister_probe("drop")
            assert not wd.unregister_probe("drop")

            class Owner:
                pass

            o = Owner()
            wd.register_probe("owned", lambda: {}, owner=o)
            del o
            gc.collect()
            # registration prunes dead-owner probes without invoking any
            wd.register_probe("fresh", lambda: {})
            assert "owned" not in wd._probes
            assert {"keep", "fresh"} <= set(wd._probes)
        finally:
            wd.shutdown()

    def test_engine_probe_unregisters_on_gc(self, model):
        """The probe-leak satellite: dead engines must not accumulate
        probes (or health providers) across lifetimes."""
        wd = CommWatchdog(timeout=30, on_timeout=lambda t, w: None)
        try:
            import paddle_tpu.distributed.watchdog as wmod

            old = wmod._singleton
            wmod._singleton = wd
            try:
                eng = Engine(model, EngineConfig(
                    max_batch_slots=1, max_model_len=16, page_size=8,
                ))
                name = f"serving.engine.{eng.engine_id}"
                assert name in wd._probes
                assert name in obs.health_snapshot()["providers"]
                del eng
                gc.collect()
                assert name not in wd._probes
                assert name not in obs.health_snapshot()["providers"]
            finally:
                wmod._singleton = old
        finally:
            wd.shutdown()


class TestScrapeEndpoint:
    @pytest.fixture(autouse=True)
    def _isolated_providers(self, monkeypatch):
        """Other tests' engines register health providers process-wide;
        these tests assert aggregate status, so start from none."""
        from paddle_tpu.observability import scrape

        monkeypatch.setattr(scrape, "_providers", {})

    def test_metrics_and_healthz(self):
        obs.counter("paddle_tpu_scrape_probe_total").inc()
        with obs.start_scrape_server() as srv:
            body = urllib.request.urlopen(
                srv.url + "/metrics", timeout=10
            ).read().decode()
            assert "paddle_tpu_scrape_probe_total 1" in body
            with urllib.request.urlopen(
                srv.url + "/healthz", timeout=10
            ) as resp:
                assert resp.status == 200
                assert json.load(resp)["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url + "/nope", timeout=10)
            assert ei.value.code == 404

    def test_healthz_aggregates_and_503s(self):
        obs.register_health_provider(
            "t.bad", lambda: {"status": "overloaded"}
        )
        obs.register_health_provider("t.dead", lambda: None)
        try:
            snap = obs.health_snapshot()
            assert snap["status"] == "overloaded"
            assert "t.dead" not in snap["providers"]  # pruned
            with obs.start_scrape_server() as srv:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        srv.url + "/healthz", timeout=10
                    )
                assert ei.value.code == 503
                assert json.loads(ei.value.read())[
                    "providers"]["t.bad"]["status"] == "overloaded"
        finally:
            obs.unregister_health_provider("t.bad")
            obs.unregister_health_provider("t.dead")

    def test_scrape_fault_degrades_to_500_and_recovers(self):
        with obs.start_scrape_server() as srv:
            spec = FaultSpec(OSError("exporter down"), at=1)
            with faults.inject({"obs.export": spec}) as inj:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        srv.url + "/metrics", timeout=10
                    )
                assert ei.value.code == 500
            assert inj.fired["obs.export"] == 1
            # server survives; next scrape is clean
            assert urllib.request.urlopen(
                srv.url + "/metrics", timeout=10
            ).status == 200


class TestServingTelemetry:
    """Acceptance: a serving run with telemetry enabled is bit-identical,
    triggers zero extra compiles, and the per-step telemetry cost is
    < 2% of the measured decode step time."""

    PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [8, 9], [10, 11, 12]]

    def _run(self, engine):
        outs = engine.generate(
            self.PROMPTS, SamplingParams(max_new_tokens=4)
        )
        return [o.token_ids for o in outs]

    def test_zero_new_compiles_and_bit_parity_under_scrape(self, engine):
        baseline = self._run(engine)   # warm every program
        m = engine.metrics
        compiles = (m.prefill_compiles, m.decode_compiles)
        retraces0 = jit_events.retraces_after_warmup("serving")
        with obs.start_scrape_server() as srv:
            scraped = []
            for _ in range(3):
                telemetry = self._run(engine)
                scraped.append(urllib.request.urlopen(
                    srv.url + "/metrics", timeout=10
                ).read().decode())
                assert telemetry == baseline
        assert (m.prefill_compiles, m.decode_compiles) == compiles
        assert jit_events.retraces_after_warmup("serving") == retraces0
        # the registry view exports this engine's series, labeled
        sid = f'engine="{engine.engine_id}"'
        assert any(
            f"paddle_tpu_serving_decode_steps_total{{{sid}}}" in s
            for s in scraped
        )

    def test_per_step_telemetry_cost_under_2pct(self, engine):
        """Structural overhead bound: what telemetry ADDS to one decode
        step (a span + a compile-log watch) must cost < 2% of the
        measured warm step time. Measured as pure host-side work so the
        bound holds on a noisy CI box; end to end on the chip it is not
        measured."""
        engine.generate([[1, 2, 3]], SamplingParams(max_new_tokens=4))
        reps = 200

        def telemetry_once():
            with obs.span("serving.decode", active=2), jit_events.watch(
                "serving.decode", kind="serving",
                signature="any_sample=False",
            ):
                pass

        for _ in range(20):   # warm the path
            telemetry_once()
        per_step_overhead = None
        for _ in range(5):    # best-of-5: shared CI boxes are noisy
            t0 = time.perf_counter()
            for _ in range(reps):
                telemetry_once()
            dt = (time.perf_counter() - t0) / reps
            if per_step_overhead is None or dt < per_step_overhead:
                per_step_overhead = dt

        # warm decode step time: drive the engine directly
        engine.add_request([1, 2, 3], SamplingParams(max_new_tokens=8))
        engine.step()      # prefill + first decode
        t0 = time.perf_counter()
        steps = 0
        while engine.has_unfinished():
            engine.step()
            steps += 1
        step_time = (time.perf_counter() - t0) / max(1, steps)
        assert per_step_overhead < 0.02 * step_time, (
            f"telemetry adds {per_step_overhead*1e6:.1f}us to a "
            f"{step_time*1e3:.2f}ms step"
        )

    def test_degradation_events_land_in_flight_ring(self, engine):
        with faults.inject(
            {"serving.step": FaultSpec(RuntimeError("poison"), at=1)}
        ):
            outs = engine.generate(
                [[1, 2], [3, 4]], SamplingParams(max_new_tokens=2)
            )
        assert sorted(o.finish_reason for o in outs) == [
            "error", "length"
        ]
        evs = obs.get_flight_recorder().events()
        assert any(
            e["category"] == "serving" and e["name"] == "error"
            and e.get("engine") == engine.engine_id
            for e in evs
        )

    def test_engine_view_unregisters_after_gc(self, model):
        eng = Engine(model, EngineConfig(
            max_batch_slots=1, max_model_len=16, page_size=8,
        ))
        key = f"engine={eng.engine_id}"
        eng.metrics.requests_received = 1
        assert any(
            key in k for k in obs.get_registry().snapshot()
        )
        del eng
        gc.collect()
        assert not any(
            key in k for k in obs.get_registry().snapshot()
        )


class TestProfilerExportProtobuf:
    def test_distinct_artifact_dir(self, tmp_path):
        from paddle_tpu import profiler

        d = str(tmp_path)
        chrome = profiler.export_chrome_tracing(d)
        with pytest.warns(UserWarning, match="xplane"):
            proto = profiler.export_protobuf(d)
        assert chrome.dir_name == d
        assert proto.dir_name == os.path.join(d, "protobuf")
        assert proto.dir_name != chrome.dir_name


class TestResilienceTelemetry:
    def test_fault_fires_counted_and_recorded(self):
        reg = obs.get_registry()
        key = "paddle_tpu_resilience_fault_fires_total{site=obs.test}"
        before = reg.snapshot().get(key, 0)
        with faults.inject({"obs.test": FaultSpec(OSError, every=1)}):
            for _ in range(2):
                with pytest.raises(OSError):
                    faults.fire("obs.test", ctx=1)
        assert reg.snapshot()[key] == before + 2
        assert any(
            e["category"] == "fault" and e["name"] == "obs.test"
            for e in obs.get_flight_recorder().events()
        )

    def test_retries_counted(self):
        from paddle_tpu.resilience import RetryPolicy

        reg = obs.get_registry()
        key = ("paddle_tpu_resilience_retries_total"
               "{exc=ConnectionError,fn=flaky}")
        before = reg.snapshot().get(key, 0)
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise ConnectionError("transient")
            return "ok"

        policy = RetryPolicy(
            max_attempts=5, base_delay=0.0, max_delay=0.0, jitter=0.0,
            sleep=lambda s: None,
        )
        assert policy.call(flaky) == "ok"
        assert reg.snapshot()[key] == before + 2
