"""Latency digests, request timelines, SLO burn, and the access log.

Compile-lean (tier-1 budget): TWO module-scoped tiny-Llama engines — a
plain baseline and a fully-instrumented one (prefix cache + chunked
prefill + speculation + access log) — plus one 2-slot 2-replica fleet
with single-bucket prefill. Everything else is host-side (digest math,
SLO windows, access-log files, journal replay anchoring).
"""
import gc
import json
import os
import re
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability.latency import (
    LatencyDigest,
    SLOConfig,
    SLOTracker,
    histogram_family,
    summary_family,
)
from paddle_tpu.resilience import FaultSpec, faults
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams
from paddle_tpu.serving.access_log import (
    AccessLog,
    iter_records,
    resolve_access_log,
)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


@pytest.fixture(scope="module")
def alog_dir(tmp_path_factory):
    return str(tmp_path_factory.mktemp("alog"))


@pytest.fixture(scope="module")
def plain_engine(model):
    return Engine(model, EngineConfig(
        max_batch_slots=4, max_model_len=32, page_size=4,
        num_blocks=32, prefill_buckets=[16, 32],
    ))


@pytest.fixture(scope="module")
def obs_engine(model, alog_dir):
    # the acceptance configuration: timelines (always on) + access log
    # + chunked prefill + prefix cache + speculation, all at once
    return Engine(model, EngineConfig(
        max_batch_slots=4, max_model_len=32, page_size=4,
        num_blocks=32, prefill_buckets=[16, 32],
        enable_prefix_cache=True, prefill_chunk_tokens=8,
        max_prefill_chunks_per_step=2, speculate_tokens=2,
        access_log=alog_dir,
    ))


def _workload(n_req=32, n_sampled=4):
    """Mixed greedy + sampled, heterogeneous lengths, prompt+new=16."""
    rng = np.random.default_rng(7)
    lens = [int(n) for n in rng.choice([4, 7, 10, 13], n_req)]
    prompts = [rng.integers(1, 128, n).tolist() for n in lens]
    params = [
        SamplingParams(max_new_tokens=16 - lens[i],
                       do_sample=(i < n_sampled), seed=i)
        for i in range(n_req)
    ]
    return prompts, params


class TestLatencyDigest:
    def test_quantile_accuracy_known_distribution(self):
        rng = np.random.default_rng(0)
        vals = rng.lognormal(-3.0, 1.0, 20000)
        d = LatencyDigest()
        for v in vals:
            d.record(v)
        assert d.count == len(vals)
        assert abs(d.sum - vals.sum()) < 1e-6 * vals.sum()
        for q in (0.5, 0.9, 0.99):
            true = float(np.quantile(vals, q))
            est = d.quantile(q)
            # error bound: half a x1.09 bucket (~4.5%)
            assert abs(est - true) / true < 0.045, (q, true, est)

    def test_cross_replica_merge_equals_pooled(self):
        rng = np.random.default_rng(1)
        vals = rng.exponential(0.05, 5000)
        pooled = LatencyDigest()
        shards = [LatencyDigest() for _ in range(4)]
        for i, v in enumerate(vals):
            pooled.record(v)
            shards[i % 4].record(v)
        merged = LatencyDigest()
        for s in shards:
            merged.merge(s)
        pc, pn, ps, pm = pooled.snapshot()
        mc, mn, ms, mm = merged.snapshot()
        assert (pc, pn, pm) == (mc, mn, mm)   # counts + max exact
        assert abs(ps - ms) < 1e-9 * abs(ps)  # sum to fp rounding
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            assert merged.quantile(q) == pooled.quantile(q)

    def test_merge_scheme_mismatch_raises(self):
        with pytest.raises(ValueError, match="bucket schemes"):
            LatencyDigest(growth=1.09).merge(LatencyDigest(growth=1.5))

    def test_empty_and_floor(self):
        d = LatencyDigest()
        assert d.count == 0 and d.quantile(0.5) is None
        assert d.mean is None
        d.record(0.0)   # a 0s queue wait is a real observation
        assert d.count == 1
        assert d.quantile(0.5) == d.min_value
        with pytest.raises(ValueError):
            d.quantile(1.5)

    def test_families_render_labels(self):
        d = LatencyDigest()
        for v in (0.01, 0.02, 0.4):
            d.record(v)
        fam = summary_family(
            "x_seconds", {"ttft": d, "tpot": LatencyDigest()},
            {"engine": "9"},
        )
        assert fam.kind == "summary"
        labels = [s[1] for s in fam.samples]
        # empty tpot digest exports nothing; ttft exports quantiles
        assert all(lb["phase"] == "ttft" for lb in labels)
        qs = {lb.get("quantile") for lb in labels if "quantile" in lb}
        assert qs == {"0.5", "0.9", "0.99"}
        assert {s[0] for s in fam.samples} == {"", "_sum", "_count"}
        hist = histogram_family("x_hist_seconds", {"ttft": d})
        assert hist.kind == "histogram"
        inf = [s for s in hist.samples
               if s[0] == "_bucket" and s[1]["le"] == "+Inf"]
        assert inf[0][2] == 3


class TestSLOTracker:
    CFG = dict(ttft_p99_ms=100.0, tpot_p99_ms=20.0, window_s=60.0,
               min_samples=5)

    def test_burn_math_and_threshold(self):
        t = SLOTracker(SLOConfig(**self.CFG))
        # 10 requests, 1 ttft violation -> 10% violating / 1% budget
        for i in range(10):
            t.record(ttft_s=0.5 if i == 0 else 0.01, tpot_s=0.005,
                     now=100.0 + i)
        rates = t.burn_rates(now=110.0)
        assert rates["ttft"] == pytest.approx(10.0)
        assert rates["tpot"] == 0.0
        assert t.burning(now=110.0)   # 10x burn, >= min_samples

    def test_min_samples_gates_sustained(self):
        t = SLOTracker(SLOConfig(**self.CFG))
        for i in range(3):   # violating, but under the sample floor
            t.record(ttft_s=9.0, now=100.0 + i)
        assert t.burn_rates(now=103.0)["ttft"] == pytest.approx(100.0)
        assert not t.burning(now=103.0)

    def test_window_expiry(self):
        t = SLOTracker(SLOConfig(**self.CFG))
        for i in range(10):
            t.record(ttft_s=9.0, now=100.0 + i)
        assert t.burning(now=105.0)
        assert t.window_counts(now=500.0) == {}
        assert not t.burning(now=500.0)
        assert t.burn_rates(now=500.0)["ttft"] is None

    def test_config_validation(self):
        with pytest.raises(ValueError, match="at least one target"):
            SLOConfig()
        with pytest.raises(ValueError):
            SLOConfig(ttft_p99_ms=-1)
        with pytest.raises(ValueError):
            SLOConfig(ttft_p99_ms=100, window_s=0)
        with pytest.raises(ValueError):
            SLOConfig(ttft_p99_ms=100, objective=1.0)
        with pytest.raises(TypeError):
            SLOTracker({"ttft_p99_ms": 100})


class TestAccessLog:
    def _rec(self, i):
        return {"rid": i, "finish_reason": "length", "ttft_s": 0.01}

    def test_rotation_and_keep_files(self, tmp_path):
        al = AccessLog(tmp_path, rotate_bytes=200, keep_files=3)
        for i in range(50):
            al.log(self._rec(i))
        files = al.files()
        assert 1 < len(files) <= 3
        assert al.rotations > 0 and al.write_errors == 0
        # the survivors hold the TAIL of the stream
        recs = list(iter_records(tmp_path))
        assert recs[-1]["rid"] == 49
        al.close()

    def test_reader_skips_torn_tail(self, tmp_path):
        al = AccessLog(tmp_path)
        for i in range(5):
            al.log(self._rec(i))
        al.close()
        # simulate the SIGKILL torn line + a damaged middle line
        path = os.path.join(tmp_path, al.files()[-1])
        with open(path, "ab") as f:
            f.write(b'{"rid": 99, "tr')   # partial write, no newline
        recs = list(iter_records(tmp_path))
        assert [r["rid"] for r in recs] == [0, 1, 2, 3, 4]

    def test_fault_degrades_never_raises(self, tmp_path):
        al = AccessLog(tmp_path)
        spec = FaultSpec(OSError("disk gone"), every=1)
        with faults.inject({"obs.accesslog": spec}):
            with pytest.warns(UserWarning, match="lossy access log"):
                al.log(self._rec(0))
            al.log(self._rec(1))   # counted, not warned again
        assert al.write_errors == 2 and al.records_written == 0
        al.log(self._rec(2))       # recovers once the fault clears
        assert al.records_written == 1
        al.close()

    def test_resolve_shares_per_directory(self, tmp_path):
        a = resolve_access_log(str(tmp_path))
        b = resolve_access_log(str(tmp_path))
        assert a is b
        assert resolve_access_log(a) is a
        with pytest.raises(ValueError):
            AccessLog(tmp_path, rotate_bytes=0)

    def test_offline_summarizer_mirrors_live_abort_contract(
        self, tmp_path, capsys,
    ):
        """queue/ttft are event-time samples (a request aborted AFTER
        admission / first token keeps them live), e2e/tpot and the SLO
        burn window are finish-time and exclude aborts — the offline
        ``slo --access-log`` view must report the same counts the live
        scrape would for the same traffic."""
        from paddle_tpu.observability.__main__ import main

        al = AccessLog(tmp_path)
        for _ in range(2):
            al.log({"finish_reason": "length", "queue_wait_s": 0.01,
                    "ttft_s": 0.02, "tpot_s": 0.001, "e2e_s": 0.05})
        # an abort with a BLOWN ttft (5s vs the 1s target below): the
        # sample belongs in the ttft digest but not in the burn window
        al.log({"finish_reason": "aborted", "queue_wait_s": 0.01,
                "ttft_s": 5.0, "tpot_s": 0.001, "e2e_s": 5.0})
        al.close()
        assert main(["slo", "--access-log", str(tmp_path),
                     "--ttft-p99-ms", "1000"]) == 0
        text = capsys.readouterr().out
        counts = {
            m.group(1): int(m.group(2)) for m in re.finditer(
                r"offline\s+(\w+)(?:\s+\S+){3}\s+(\d+)", text
            )
        }
        assert counts == {"queue": 3, "ttft": 3, "tpot": 2, "e2e": 2}
        assert "burn[ttft] vs p99 target: 0.00x" in text


class TestServingTimelines:
    """Acceptance: a mixed workload (greedy + sampled, chunked prefill
    + speculation on) with timelines and access logging enabled is
    byte-identical on greedy outputs, compiles nothing new on a warm
    engine, and exposes non-empty latency series on a scrape."""

    def test_parity_zero_new_compiles_scrape_and_access_log(
        self, plain_engine, obs_engine, alog_dir,
    ):
        prompts, params = _workload()
        base = plain_engine.generate(prompts, params)
        first = obs_engine.generate(prompts, params)   # warm everything
        m = obs_engine.metrics
        compiles = (
            m.prefill_compiles, m.prefill_ext_compiles,
            m.decode_compiles, m.cow_compiles, m.verify_compiles,
        )
        lines0 = obs_engine.access_log.records_written
        outs = obs_engine.generate(prompts, params)
        # zero new compiles on the warm engine, with everything on
        assert (
            m.prefill_compiles, m.prefill_ext_compiles,
            m.decode_compiles, m.cow_compiles, m.verify_compiles,
        ) == compiles
        # greedy outputs byte-identical to the plain baseline (and to
        # the first instrumented run); sampled slots draw from the
        # engine key stream, so only their bookkeeping is asserted
        for b, f, o, p in zip(base, first, outs, params):
            if not p.do_sample:
                assert o.token_ids == b.token_ids == f.token_ids
            assert o.finish_reason in ("length", "stop")
        # one access-log line per finished request
        assert (
            obs_engine.access_log.records_written - lines0 == len(outs)
        )
        recs = list(iter_records(alog_dir))
        rids = {r["rid"] for r in recs}
        assert all(o.request_id in rids for o in outs)
        # RequestOutput.metrics: the phase breakdown + counters
        mt = outs[0].metrics
        assert mt["queue_wait_s"] >= 0
        assert mt["ttft_s"] >= mt["queue_wait_s"]
        assert mt["e2e_s"] >= mt["ttft_s"]
        assert mt["decode_tokens"] == len(outs[0].token_ids) - 1
        assert mt["finish_reason"] == outs[0].finish_reason
        # scrape exposes non-empty percentile series for every phase
        with obs.start_scrape_server() as srv:
            text = urllib.request.urlopen(
                srv.url + "/metrics", timeout=10
            ).read().decode()
        eid = obs_engine.engine_id
        for phase in ("ttft", "tpot", "e2e", "queue"):
            for q in ("0.5", "0.9", "0.99"):
                needle = (
                    f'paddle_tpu_serving_latency_seconds{{'
                    f'engine="{eid}",phase="{phase}",quantile="{q}"}}'
                )
                assert needle in text, needle
        assert (
            f'paddle_tpu_serving_latency_hist_seconds_bucket{{'
            f'engine="{eid}",le="+Inf",phase="ttft"}}' in text
        )

    def test_timeline_counters_chunks_prefix_spec(
        self, obs_engine, monkeypatch,
    ):
        # long repeated prompt: >1 chunk, prefix hits on the second
        # pass; the second pass runs under an oracle-fed drafter (the
        # first pass's own greedy tokens) so verify launches — and
        # accepted drafts — happen deterministically
        from paddle_tpu.serving import engine as engine_mod

        prompt = list(range(1, 9)) * 3   # 24 tokens, chunk=8
        p = SamplingParams(max_new_tokens=6)
        out1 = obs_engine.generate([prompt], p)[0]
        ref = out1.token_ids

        def feeding(history, k, **kw):
            h = [int(t) for t in history]
            for m in range(min(len(ref) - 1, len(h)), 0, -1):
                if h[-m:] == ref[:m]:
                    return ref[m: m + k]
            return []

        monkeypatch.setattr(engine_mod.speculation, "propose", feeding)
        out2 = obs_engine.generate([prompt], p)[0]
        assert out1.token_ids == out2.token_ids
        assert out1.metrics["prefill_chunks"] >= 2
        assert out1.metrics["prefill_tokens"] >= 23
        assert out2.metrics["prefix_hit_tokens"] > 0
        assert out2.metrics["verify_steps"] >= 1
        assert out2.metrics["spec_accepted"] >= 1
        assert out2.metrics["decode_tokens"] == len(ref) - 1
        # digest bookkeeping: ttft fed once per request
        assert obs_engine.metrics.latency["ttft"].count >= 2

    def test_mean_ttft_derived_from_digest(self, obs_engine):
        # under xdist's load distribution this may be the first test to
        # touch its worker's engine: serve one request of its own
        obs_engine.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))
        m = obs_engine.metrics
        d = m.latency["ttft"]
        assert m.mean_ttft == pytest.approx(d.sum / d.count)
        assert m.snapshot()["mean_ttft_s"] == m.mean_ttft

    def test_finished_timelines_land_in_flight_ring(self, obs_engine):
        before = {
            t["rid"] for t in obs.flight.timelines()
        }
        out = obs_engine.generate(
            [[5, 6, 7]], SamplingParams(max_new_tokens=2)
        )[0]
        tls = obs.flight.timelines()
        mine = [t for t in tls if t["rid"] == out.request_id
                and t["rid"] not in before]
        assert mine and mine[0]["finish_reason"] == out.finish_reason
        assert mine[0]["engine"] == obs_engine.engine_id
        # and a postmortem carries them
        dump_payload = None
        path = obs.dump("test-timelines")
        try:
            with open(path) as f:
                dump_payload = json.load(f)
        finally:
            os.remove(path)
        assert any(
            t.get("rid") == out.request_id
            for t in dump_payload["request_timelines"]
        )


class TestSLOHealthFlip:
    def test_sustained_burn_degrades_health_and_healthz(
        self, obs_engine,
    ):
        tracker = SLOTracker(SLOConfig(
            ttft_p99_ms=1e-6, window_s=60.0, min_samples=2,
        ))
        obs_engine.slo = tracker
        obs_engine.metrics.slo = tracker
        try:
            obs_engine.generate(
                [[1, 2], [3, 4], [5, 6]],
                SamplingParams(max_new_tokens=2),
            )
            h = obs_engine.health()
            assert "slo_burn" in h["flags"]
            assert "degraded" in h["flags"]
            assert h["slo_burn_rates"]["ttft"] >= 1.0
            with obs.start_scrape_server() as srv:
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(
                        srv.url + "/healthz", timeout=10
                    )
                assert ei.value.code == 503
                body = json.loads(ei.value.read().decode())
                assert body["status"] == "degraded"
                text = urllib.request.urlopen(
                    srv.url + "/metrics", timeout=10
                ).read().decode()
            eid = obs_engine.engine_id
            assert (
                f'paddle_tpu_serving_slo_burning{{engine="{eid}"}} 1'
                in text
            )
        finally:
            obs_engine.slo = None
            obs_engine.metrics.slo = None
        assert "slo_burn" not in obs_engine.health()["flags"]


class TestFleetMergedDigestsAndBurn:
    def test_merged_view_pooled_burn_and_degraded_health(self, model):
        fleet = serving.Fleet(model, EngineConfig(
            max_batch_slots=2, max_model_len=16, page_size=8,
            slo=SLOConfig(ttft_p99_ms=1e-6, window_s=60.0,
                          min_samples=3),
        ), serving.FleetConfig(num_replicas=2, analysis_check=None))
        outs = fleet.generate(
            [[1, 2, 3], [4, 5], [6, 7, 8], [9, 10]],
            SamplingParams(max_new_tokens=3),
        )
        assert len(outs) == 4
        engines = [s.engine for s in fleet.replicas
                   if s.engine is not None]
        merged = fleet.merged_latency()
        assert merged["ttft"].count == sum(
            e.metrics.latency["ttft"].count for e in engines
        ) == 4
        # pooled window counts across replicas -> fleet-level burn,
        # even though each replica alone may sit under min_samples
        rates = fleet.slo_burn_rates()
        assert rates["ttft"] >= 1.0
        assert fleet.slo_burning()
        h = fleet.health()
        assert h["status"] == "degraded" and h["slo_burn"]
        # the registry carries the fleet-merged series + burn gauges
        text = obs.get_registry().render_prometheus()
        fid = fleet.fleet_id
        assert (
            f'paddle_tpu_serving_latency_seconds{{fleet="{fid}",'
            f'phase="ttft",quantile="0.99"}}' in text
        )
        assert (
            f'paddle_tpu_fleet_slo_burning{{fleet="{fid}"}} 1' in text
        )
        # a request that finishes WITHOUT reaching an engine (parked
        # timeout / pending abort) still lands in the merged digests
        # and the SLO pool — the overload tail must not vanish
        freq = serving.FleetRequest(
            [1, 2, 3], SamplingParams(max_new_tokens=2), "local-0"
        )
        n0 = fleet.merged_latency()["e2e"].count
        fleet._finish_local(freq, "timeout")
        assert freq.output.finish_reason == "timeout"
        assert freq.output.metrics["e2e_s"] is not None
        assert fleet.merged_latency()["e2e"].count == n0 + 1
        del fleet, engines
        gc.collect()
        text = obs.get_registry().render_prometheus()
        assert f'fleet="{fid}",phase="ttft"' not in text


class TestReplayTimelineCoherence:
    def test_recovered_request_anchors_journaled_arrival(
        self, tmp_path,
    ):
        from paddle_tpu.serving.journal import Journal, restore_entries
        from paddle_tpu.serving.request import Request

        j = Journal(str(tmp_path))
        req = Request([1, 2, 3],
                      SamplingParams(max_new_tokens=8, ttl_s=60))
        j.admit(req)
        # pretend the admission happened 5s before the "crash"
        j._buffer[-1]["ts"] = time.time() - 5.0
        j.flush(force=True)
        j.close()

        j2 = Journal(str(tmp_path))
        live, expired = restore_entries(
            j2, j2.replay(),
            lambda e, p: Request(e.prompt, p, request_id=e.rid),
        )
        assert expired == 0 and len(live) == 1
        r = live[0]
        age = time.perf_counter() - r.arrival_time
        # arrival anchored at the journaled wall clock: a TTFT/e2e
        # sample for this request now INCLUDES the downtime instead of
        # reading impossibly fast
        assert 4.0 < age < 7.0
        assert r.timeline.recovered
        assert r.timeline.arrival == r.arrival_time
        # and the TTL deadline agrees with the same anchor
        assert 50.0 < r.deadline - time.perf_counter() < 56.0
