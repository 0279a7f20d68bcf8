"""paddle_tpu.serving: continuous-batching engine + paged KV-cache.

Deterministic CPU suite (seeded arrivals, tiny Llama): the acceptance
criteria of the serving subsystem are asserted directly —

  * >= 32 concurrent requests with heterogeneous prompt/output lengths
    through ONE fixed-shape compiled decode step (compile-count probe:
    the counters are bumped inside the traced bodies, so they move only
    when XLA retraces);
  * requests join and leave the batch mid-flight (staggered admissions,
    slot reuse);
  * KV blocks are freed on completion (pool high-water mark < aggregate
    demand, used == 0 after drain);
  * per-request greedy outputs are BIT-IDENTICAL to running the same
    requests one-at-a-time through ``generation.GenerationMixin``.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import FaultSpec, faults
from paddle_tpu.serving import (
    BlockManager,
    Engine,
    EngineConfig,
    EngineOverloadedError,
    SamplingParams,
)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _generate_oracle(model, prompt, max_new):
    """The single-stream reference: one request at a time through
    generate()."""
    ids = paddle.to_tensor(np.array([prompt], dtype="int64"))
    out = model.generate(ids, max_new_tokens=max_new)
    return out.numpy()[0, len(prompt):].tolist()


class TestBlockManager:
    def test_allocate_free_cycle(self):
        bm = BlockManager(num_blocks=8, block_size=4)
        a = bm.allocate(3)
        assert bm.num_used == 3 and bm.num_free == 5
        assert bm.high_water == 3
        b = bm.allocate(2)
        assert bm.high_water == 5
        bm.free(a)
        assert bm.num_used == 2
        bm.free(b)
        assert bm.num_used == 0 and bm.num_free == 8
        assert bm.high_water == 5  # sticky

    def test_refcount_fork(self):
        bm = BlockManager(4, 4)
        a = bm.allocate(2)
        bm.fork(a)  # second owner (prefix sharing)
        bm.free(a)
        assert bm.num_used == 2  # still referenced
        bm.free(a)
        assert bm.num_used == 0
        with pytest.raises(RuntimeError, match="double free"):
            bm.free(a)

    def test_exhaustion_and_needed(self):
        bm = BlockManager(2, 4)
        assert bm.blocks_needed(1) == 1
        assert bm.blocks_needed(4) == 1
        assert bm.blocks_needed(5) == 2
        bm.allocate(2)
        assert not bm.can_allocate(1)
        with pytest.raises(RuntimeError, match="exhausted"):
            bm.allocate(1)


class TestSamplingParams:
    def test_validation(self):
        with pytest.raises(ValueError):
            SamplingParams(max_new_tokens=0)
        with pytest.raises(ValueError):
            SamplingParams(temperature=0.0)
        with pytest.raises(ValueError):
            SamplingParams(top_p=0.0)
        with pytest.raises(ValueError):
            SamplingParams(top_k=-1)
        p = SamplingParams(eos_token_id=5, stop_token_ids=[7, 9])
        assert p.stop_ids == {5, 7, 9}

    def test_batched_warp_matches_scalar_warp(self):
        """serving's per-slot vector warp must equal generation's scalar
        warp row by row (same implementation, batched params)."""
        from paddle_tpu.generation import warp_logits

        rng = np.random.default_rng(0)
        logits = rng.standard_normal((4, 32)).astype("float32")
        temps = [0.7, 1.0, 1.3, 0.9]
        ks = [5, 0, 12, 3]
        ps = [0.8, 1.0, 0.5, 0.95]
        batched = np.asarray(warp_logits(
            logits, np.array(temps, "float32"), np.array(ks, "int32"),
            np.array(ps, "float32"),
        ))
        for i in range(4):
            row = np.asarray(
                warp_logits(logits[i:i + 1], temps[i], ks[i], ps[i])
            )
            np.testing.assert_allclose(batched[i], row[0], rtol=1e-6)


def _mixed_workload(n_req=32):
    """The acceptance workload: heterogeneous (prompt, output) lengths
    drawn from few DISTINCT combos, all with prompt+new = 16: the
    one-at-a-time oracle compiles one generate program per distinct
    (prompt_len, prompt_len+max_new) pair (~2s each), which would
    otherwise dominate the test. The ENGINE is combo-blind either way —
    its decode step never recompiles (asserted below)."""
    rng = np.random.default_rng(42)
    lens = [int(n) for n in rng.choice([4, 7, 10, 13], n_req)]
    prompts = [rng.integers(1, 128, n).tolist() for n in lens]
    max_new = [16 - n for n in lens]
    # seeded arrival schedule: 8 up front, the rest join mid-flight
    arrivals = sorted(
        [0] * 8 + rng.integers(1, 20, n_req - 8).tolist()
    )
    return prompts, max_new, arrivals


class TestMixedWorkload:
    """32 heterogeneous requests, 4 slots, staggered (seeded) arrivals,
    pool smaller than aggregate demand."""

    N_REQ = 32

    def _workload(self):
        return _mixed_workload(self.N_REQ)

    def test_mixed_workload_parity_and_fixed_shapes(self, model):
        prompts, max_new, arrivals = self._workload()
        cfg = EngineConfig(
            max_batch_slots=4, max_model_len=32, page_size=4,
            num_blocks=16, prefill_buckets=[16, 32],
        )
        engine = Engine(model, cfg)
        bm = engine.block_manager
        # aggregate KV demand far exceeds the pool: only block FREEING on
        # completion lets the workload drain
        demand = sum(
            bm.blocks_needed(len(p) + k)
            for p, k in zip(prompts, max_new)
        )
        assert demand > cfg.num_blocks

        done = {}
        pending = list(zip(prompts, max_new, arrivals))
        step = 0
        max_running = 0
        submitted = []
        while pending or engine.has_unfinished():
            while pending and pending[0][2] <= step:
                p, k, _ = pending.pop(0)
                submitted.append(
                    engine.add_request(p, SamplingParams(max_new_tokens=k))
                )
            for out in engine.step():
                done[out.request_id] = out
            max_running = max(max_running, engine.metrics.num_running)
            step += 1
            assert step < 500, "engine failed to drain"

        assert len(done) == self.N_REQ
        assert max_running == cfg.max_batch_slots  # batch actually filled
        # ONE decode program, at most one prefill program per bucket —
        # i.e. no recompile after warmup (counters bump only on trace)
        assert engine.metrics.decode_compiles == 1
        assert engine.metrics.prefill_compiles <= len(cfg.prefill_buckets)
        # KV blocks all returned; high-water proves reuse under pressure
        assert bm.num_used == 0
        assert 0 < bm.high_water <= cfg.num_blocks
        assert engine.metrics.snapshot()["preemptions"] >= 0

        # bit-identical to the single-stream path, request by request
        for req, p, k in zip(submitted, prompts, max_new):
            ref = _generate_oracle(model, p, k)
            assert done[req.request_id].token_ids == ref, req.request_id

    def test_preemption_is_transparent(self, model):
        """A pool too small for the running set forces recompute-style
        preemption; greedy outputs must be unchanged by it."""
        rng = np.random.default_rng(7)
        # (prompt, output) combos from the mixed-workload family: the
        # oracle reuses its already-compiled generate programs
        lens = [int(n) for n in rng.choice([4, 7, 10], 6)]
        prompts = [rng.integers(1, 128, n).tolist() for n in lens]
        max_new = [16 - n for n in lens]
        cfg = EngineConfig(
            max_batch_slots=4, max_model_len=32, page_size=4,
            num_blocks=10, prefill_buckets=[32],
        )
        engine = Engine(model, cfg)
        outs = engine.generate(
            prompts,
            [SamplingParams(max_new_tokens=k) for k in max_new],
        )
        assert engine.metrics.preemptions >= 1
        assert engine.block_manager.num_used == 0
        for o, p, k in zip(outs, prompts, max_new):
            assert o.token_ids == _generate_oracle(model, p, k)


@pytest.fixture(scope="module")
def small_engine(model):
    """Shared engine for the stop/sampling/API tests (engines drain
    completely between uses, so sharing only saves recompiles)."""
    return Engine(model, EngineConfig(
        max_batch_slots=4, max_model_len=32, page_size=4, seed=3,
    ))


class TestStopConditions:
    def test_stop_tokens_and_prefill_finish(self, model, small_engine):
        engine = small_engine
        prompt = [3, 17, 42, 99]
        # pick the token greedy decoding emits 3rd, use it as EOS
        # (max_new 12 keeps the oracle on the workload's compiled programs)
        ref = _generate_oracle(model, prompt, 12)
        out = engine.generate(
            [prompt],
            SamplingParams(max_new_tokens=12, eos_token_id=ref[2]),
        )[0]
        # the stop token is kept (generate's EOS-then-pad semantics)
        assert out.token_ids == ref[:3]
        assert out.finish_reason == "stop"
        # explicit stop_token_ids, independent of eos
        prompt2 = [5, 6, 7, 9]
        ref2 = _generate_oracle(model, prompt2, 12)
        out2 = engine.generate(
            [prompt2],
            SamplingParams(max_new_tokens=12, stop_token_ids=[ref2[1]]),
        )[0]
        assert out2.token_ids == ref2[:2]
        assert out2.finish_reason == "stop"
        # a max_new_tokens=1 request finishes AT prefill: no decode step
        before = engine.metrics.decode_steps
        out3 = engine.generate(
            [[1, 2, 3]], SamplingParams(max_new_tokens=1)
        )[0]
        assert len(out3.token_ids) == 1
        assert out3.finish_reason == "length"
        assert engine.metrics.decode_steps == before

    def test_sampling_stays_in_vocab(self, small_engine):
        outs = small_engine.generate(
            [[1, 2, 3], [4, 5], [6, 7, 8, 9]],
            SamplingParams(max_new_tokens=6, do_sample=True,
                           temperature=0.8, top_k=20, top_p=0.9),
        )
        for o in outs:
            assert len(o.token_ids) == 6
            assert all(0 <= t < 128 for t in o.token_ids)


class TestEngineAPI:
    def test_admission_limits(self, model):
        # config-validation only: the engine never runs a step, so the
        # compile cost is just trace-free construction
        engine = Engine(model, EngineConfig(
            max_batch_slots=1, max_model_len=16, page_size=4,
            max_waiting=1,
        ))
        with pytest.raises(ValueError, match="no room"):
            engine.add_request(list(range(1, 17)))
        engine.add_request([1, 2, 3])
        with pytest.raises(RuntimeError, match="queue full"):
            engine.add_request([4, 5, 6])
        # drain the queued request, then: generate() must throttle its
        # submissions against max_waiting instead of raising mid-batch
        while engine.has_unfinished():
            engine.step()
        outs = engine.generate(
            [[1, 2], [3, 4], [5, 6]], SamplingParams(max_new_tokens=2)
        )
        assert [len(o.token_ids) for o in outs] == [2, 2, 2]

    def test_abort_and_metrics(self, model, small_engine):
        engine = small_engine
        base = engine.metrics.snapshot()
        r1 = engine.add_request([1, 2], SamplingParams(max_new_tokens=8))
        r2 = engine.add_request([3, 4], SamplingParams(max_new_tokens=3))
        engine.step()  # both running
        assert engine.abort(r1.request_id)
        assert r1.finish_reason == "aborted"
        assert r1.finish_time is not None
        assert engine.block_manager.num_used > 0  # r2 still holds blocks
        assert not engine.abort(12345)
        done = {}
        while engine.has_unfinished():
            for out in engine.step():
                done[out.request_id] = out
        # the abort produced a RequestOutput from the NEXT step — a
        # driver waiting on r1 (generate, a fleet drain) unblocks
        assert done[r1.request_id].finish_reason == "aborted"
        assert done[r1.request_id].latency is not None
        assert engine.block_manager.num_used == 0
        assert r2.state is serving.RequestState.FINISHED
        snap = engine.metrics.snapshot()
        # BOTH requests finished: the abort counts
        assert snap["requests_finished"] == base["requests_finished"] + 2
        # r2: 2 prompt tokens prefilled, first token at prefill, 2 decoded
        assert snap["prefill_tokens"] >= base["prefill_tokens"] + 2
        assert snap["mean_ttft_s"] > 0
        assert snap["cache_utilization"] == 0.0
        assert snap["tokens_per_s"] > 0

    def test_invalid_configs(self, model):
        with pytest.raises(ValueError, match="cannot hold"):
            EngineConfig(max_model_len=64, page_size=4, num_blocks=2)
        with pytest.raises(ValueError, match="cover max_model_len"):
            EngineConfig(max_model_len=64, prefill_buckets=[16, 32])
        with pytest.raises(ValueError, match="max_waiting"):
            EngineConfig(max_waiting=0)
        with pytest.raises(TypeError, match="cannot serve"):
            Engine(object())

    def test_llm_predictor_facade(self, model):
        from paddle_tpu import inference

        cfg = inference.Config()
        assert not cfg.continuous_batching_enabled()
        with pytest.raises(ValueError, match="enable_continuous_batching"):
            inference.create_llm_predictor(cfg, model)
        cfg.enable_continuous_batching(
            max_batch_slots=2, max_model_len=32, page_size=4
        )
        p = inference.create_llm_predictor(cfg, model)
        outs = p.generate([[1, 2, 3, 4], [4, 5]], max_new_tokens=12)
        assert [len(o.token_ids) for o in outs] == [12, 12]
        assert outs[0].token_ids == _generate_oracle(
            model, [1, 2, 3, 4], 12
        )
        assert p.metrics()["requests_finished"] == 2


def _drain(engine):
    """Step until idle; {request_id: RequestOutput}."""
    done, guard = {}, 0
    while engine.has_unfinished():
        for out in engine.step():
            done[out.request_id] = out
        guard += 1
        assert guard < 300, "engine failed to drain"
    return done


class TestGracefulDegradation:
    """Failure containment (resilience PR): poison requests are isolated,
    TTLs expire to finish_reason="timeout", KV pressure sheds at
    add_request, and health() reports it all. Reuses the module-scope
    engine: every test drains completely, so only counters persist."""

    PROMPTS = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11]]

    def _run(self, engine, poison=None, phase="prefill"):
        params = SamplingParams(max_new_tokens=4)
        reqs = [engine.add_request(p, params) for p in self.PROMPTS]
        if poison is None:
            return reqs, _drain(engine)
        rid = reqs[poison].request_id
        if phase == "prefill":
            spec = FaultSpec(
                RuntimeError("bad weights"),
                when=lambda c: (c.get("phase") == "prefill"
                                and c.get("request_id") == rid),
            )
        else:
            # batch-level decode failure: unattributed, so the engine
            # must bisect to find the poison slot
            spec = FaultSpec(
                RuntimeError("nan logits"),
                when=lambda c: (c.get("phase") == "decode"
                                and rid in c.get("request_ids", ())),
            )
        with faults.inject({"serving.step": spec}):
            return reqs, _drain(engine)

    def test_health_starts_ok(self, small_engine):
        h = small_engine.health()
        assert h["status"] == "ok"
        assert h["flags"] == []
        assert h["queue_depth"] == 0 and h["num_running"] == 0
        assert h["watchdog"] == {"enabled": False, "fired": None}

    def test_poison_prefill_isolated_bit_identical_rest(
        self, model, small_engine
    ):
        engine = small_engine
        ref_reqs, ref = self._run(engine)
        reqs, out = self._run(engine, poison=2, phase="prefill")
        poisoned = out[reqs[2].request_id]
        assert poisoned.finish_reason == "error"
        assert "bad weights" in poisoned.error
        assert poisoned.token_ids == []
        # the other requests' greedy outputs are bit-identical to the
        # uninjected run — one poison request cannot take down the batch
        for i in (0, 1, 3):
            assert (out[reqs[i].request_id].token_ids
                    == ref[ref_reqs[i].request_id].token_ids)
        assert engine.block_manager.num_used == 0
        assert engine.metrics.requests_errored == 1
        assert engine.health()["status"] == "degraded"
        assert "degraded" in engine.health()["flags"]
        assert "bad weights" in engine.metrics.last_error

    def test_poison_decode_bisected_out(self, model, small_engine):
        engine = small_engine
        before = engine.metrics.requests_errored
        ref_reqs, ref = self._run(engine)
        reqs, out = self._run(engine, poison=1, phase="decode")
        poisoned = out[reqs[1].request_id]
        assert poisoned.finish_reason == "error"
        assert "nan logits" in poisoned.error
        # prefill succeeded, so the poison request kept its first token
        assert len(poisoned.token_ids) == 1
        for i in (0, 2, 3):
            assert (out[reqs[i].request_id].token_ids
                    == ref[ref_reqs[i].request_id].token_ids)
        assert engine.block_manager.num_used == 0
        assert engine.metrics.requests_errored == before + 1

    def test_attributed_decode_failure_skips_bisection(
        self, model, small_engine
    ):
        engine = small_engine
        params = SamplingParams(max_new_tokens=3)
        reqs = [engine.add_request(p, params) for p in self.PROMPTS[:3]]
        rid = reqs[0].request_id

        def attributed(_ctx):
            e = RuntimeError("lora swap failed")
            e.request_id = rid
            raise e

        launches = []
        spec = FaultSpec(
            action=attributed,
            when=lambda c: (c.get("phase") == "decode"
                            and rid in c.get("request_ids", ())
                            and not launches.append(len(c["request_ids"]))),
        )
        with faults.inject({"serving.step": spec}):
            out = _drain(engine)
        assert out[rid].finish_reason == "error"
        assert all(out[r.request_id].finish_reason == "length"
                   for r in reqs[1:])
        # attribution short-circuits: one full-batch launch saw the
        # poison id, no singleton bisection launches followed
        assert launches == [3]

    def test_ttl_expires_queued_and_running(self, model, small_engine):
        engine = small_engine
        dead = engine.add_request(
            [1, 2, 3], SamplingParams(max_new_tokens=4, ttl_s=0.0)
        )
        live = engine.add_request([4, 5], SamplingParams(max_new_tokens=2))
        running = engine.add_request(
            [6, 7], SamplingParams(max_new_tokens=8)
        )
        out = {o.request_id: o for o in engine.step()}
        # dead expired from the queue; others prefilled (live may even
        # have finished already)
        assert dead.finish_reason == "timeout"
        assert dead.state is serving.RequestState.FINISHED
        # expire a RUNNING request deterministically mid-flight
        running.deadline = 0.0
        out.update(_drain(engine))
        assert out[running.request_id].finish_reason == "timeout"
        assert 1 <= len(out[running.request_id].token_ids) < 8
        assert out[live.request_id].finish_reason == "length"
        assert engine.metrics.requests_timeout >= 2
        assert engine.block_manager.num_used == 0

    def test_kv_pressure_load_shedding(self, model, small_engine):
        engine = small_engine
        engine.config.kv_shed_threshold = 0.01
        try:
            params = SamplingParams(max_new_tokens=6)
            reqs = [
                engine.add_request(p, params) for p in self.PROMPTS
            ]
            engine.step()  # all four admitted: slots full, blocks held
            with pytest.raises(EngineOverloadedError, match="shed"):
                engine.add_request([1, 2], params)
            assert engine.metrics.requests_shed == 1
            h = engine.health()
            # status precedence keeps the single string (overloaded
            # masks degraded) — flags carries BOTH for the fleet router
            assert h["status"] == "overloaded"
            assert "overloaded" in h["flags"]
            if engine.metrics.requests_errored:
                # module-scope engine: earlier poison tests left it
                # degraded — overloaded must not mask that in flags
                assert "degraded" in h["flags"]
            out = _drain(engine)
            assert len(out) == len(reqs)
            # pressure released: admission works again
            ok = engine.add_request([1, 2], params)
            out = _drain(engine)
            assert out[ok.request_id].finish_reason == "length"
        finally:
            engine.config.kv_shed_threshold = None

    def test_generate_shed_retry_backs_off(self, model, small_engine):
        """When every pending prompt is shed and nothing is in
        flight, generate()'s submit loop used to spin on no-op step()
        calls; it must back off through resilience.RetryPolicy and
        resume cleanly once the pressure clears."""
        from paddle_tpu.resilience.retry import RetryPolicy

        eng = small_engine
        shed0 = eng.metrics.requests_shed
        real_submit, calls = eng.submit, {"n": 0}

        def pressured_submit(req):
            calls["n"] += 1
            if calls["n"] <= 6:   # sustained synthetic KV pressure
                eng.metrics.requests_shed += 1
                raise EngineOverloadedError("pool saturated")
            return real_submit(req)

        sleeps = []
        saved_backoff = eng._shed_backoff
        eng.submit = pressured_submit
        eng._shed_backoff = RetryPolicy(
            max_attempts=None, deadline=float("inf"),
            base_delay=0.001, max_delay=0.05, jitter=0.0, seed=0,
            sleep=sleeps.append,
        )
        try:
            outs = eng.generate(
                [[1, 2, 3], [4, 5]], SamplingParams(max_new_tokens=3),
            )
        finally:
            del eng.submit            # un-shadow the bound method
            eng._shed_backoff = saved_backoff
        # every fruitless shed iteration slept (exponential growth),
        # no spin — and the counter nets out: internal retries are
        # flow control, not client-visible rejections
        assert len(sleeps) == 6
        assert sleeps == sorted(sleeps) and sleeps[0] > 0
        assert sleeps[-1] > 4 * sleeps[0]
        assert [o.finish_reason for o in outs] == ["length"] * 2
        assert eng.metrics.requests_shed == shed0

    def test_watchdog_probe_and_health_wiring(self, model):
        from paddle_tpu.distributed.watchdog import (
            disable_comm_watchdog,
            enable_comm_watchdog,
        )

        wd = enable_comm_watchdog(timeout=30)
        try:
            eng = Engine(model, EngineConfig(
                max_batch_slots=1, max_model_len=16, page_size=4,
            ))
            assert any(
                k.startswith("serving.engine") for k in wd._probes
            )
            h = eng.health()
            assert h["watchdog"]["enabled"] and h["status"] == "ok"
        finally:
            disable_comm_watchdog()


@pytest.fixture(scope="module")
def prefix_engine(model):
    """Shared engine with automatic prefix caching AND chunked prefill
    on — the whole class drains between tests, so only counters and
    retained cache blocks persist (deltas are asserted, never
    absolutes). Program set: 3 prefill + 3 prefill_ext buckets, one
    decode, one COW — the compile probes below hold cumulatively."""
    return Engine(model, EngineConfig(
        max_batch_slots=4, max_model_len=32, page_size=4,
        num_blocks=96,   # headroom: active demand (<=32) + retained cache
        prefill_buckets=[8, 16, 32],
        enable_prefix_cache=True, prefill_chunk_tokens=8,
        max_prefill_chunks_per_step=1, seed=3,
    ))


class TestPrefixCacheChunkedPrefill:
    """Tentpole acceptance: automatic prefix caching + chunked prefill
    stay BYTE-identical to ``generate`` and to a cache-disabled engine
    whether the cache hits, misses, or is disabled, while measurably
    cutting prefill compute on shared-prefix traffic — with the compile
    probes pinning the declared program set."""

    def test_mixed_workload_parity_two_passes(self, model, prefix_engine):
        """The 32-request acceptance workload, twice: pass 1 is all
        cache misses, pass 2 re-serves identical prompts through cache
        hits (including full-prompt matches that exercise the COW cap).
        Every output of both passes byte-matches generate()."""
        engine = prefix_engine
        prompts, max_new, arrivals = _mixed_workload()
        for _pass in (1, 2):
            done = {}
            pending = list(zip(prompts, max_new, arrivals))
            step = 0
            submitted = []
            while pending or engine.has_unfinished():
                while pending and pending[0][2] <= step:
                    p, k, _ = pending.pop(0)
                    submitted.append(engine.add_request(
                        p, SamplingParams(max_new_tokens=k)
                    ))
                for out in engine.step():
                    done[out.request_id] = out
                step += 1
                assert step < 500, "engine failed to drain"
            assert len(done) == len(prompts)
            for req, p, k in zip(submitted, prompts, max_new):
                ref = _generate_oracle(model, p, k)
                assert done[req.request_id].token_ids == ref, (
                    _pass, req.request_id,
                )
        m = engine.metrics
        # pass 2 actually reused cached prefixes (and diverged via COW
        # where the one-token cap cut into a fully-matched prompt)
        assert m.prefix_hit_tokens > 0
        assert m.cow_copies >= 1
        # compile probe: ONE decode program, at most one program per
        # bucket per prefill family, one COW — zero traces beyond the
        # declared set (counters bump only inside traced bodies)
        assert m.decode_compiles == 1
        assert m.prefill_compiles <= 3
        assert m.prefill_ext_compiles <= 3
        assert m.cow_compiles <= 1
        # drained: every non-cached block returned to the free list
        bm = engine.block_manager
        assert bm.num_used == engine.prefix_cache.reclaimable_blocks()

    def test_cache_disabled_engine_byte_matches_enabled(
        self, model, small_engine, prefix_engine
    ):
        """Same prompts through the module's cache-disabled engine and
        the cache+chunking engine: byte-identical greedy outputs."""
        prompts = [[21, 22, 23, 24], [31, 32, 33], [41, 42, 43, 44, 45]]
        params = SamplingParams(max_new_tokens=6)
        plain = small_engine.generate(prompts, params)
        cached = prefix_engine.generate(prompts, params)   # miss pass
        cached2 = prefix_engine.generate(prompts, params)  # hit pass
        for a, b, c in zip(plain, cached, cached2):
            assert a.token_ids == b.token_ids == c.token_ids

    def test_shared_system_prompt_cuts_prefill_compute(
        self, model, prefix_engine
    ):
        """Perf evidence (counter-based): with a 16-token shared system
        prompt, prefill tokens COMPUTED drop by exactly the shared
        fraction once the prefix is cached."""
        engine = prefix_engine
        sys_prefix = list(range(60, 76))          # 16 tokens, 4 blocks
        warm = sys_prefix + [90, 91, 92, 93]
        params = SamplingParams(max_new_tokens=4)
        engine.generate([warm], params)           # publishes the prefix
        m = engine.metrics
        tails = [[100 + 4 * i + j for j in range(4)] for i in range(6)]
        prompts = [sys_prefix + t for t in tails]
        computed0 = m.prefill_tokens
        hit0 = m.prefix_hit_tokens
        outs = engine.generate(prompts, params)
        total = sum(len(p) for p in prompts)
        shared = 16 * len(prompts)
        # every request reused the full shared prefix: computed tokens
        # dropped by >= the shared-prefix fraction (here: exactly)
        assert m.prefix_hit_tokens - hit0 == shared
        assert m.prefill_tokens - computed0 == total - shared
        # and the reuse is bit-transparent
        for out, p in zip(outs[:2], prompts[:2]):
            assert out.token_ids == _generate_oracle(model, p, 4)

    def test_chunked_prefill_interleaves_decode(
        self, model, prefix_engine
    ):
        """A 13-token prompt (chunks of 8: two launches) must NOT stall
        the decode batch: the short request keeps producing a token
        every step while the long prompt prefills chunk by chunk."""
        engine = prefix_engine
        rng = np.random.default_rng(7)
        short_p = [int(t) for t in rng.integers(1, 128, 4)]
        long_p = [int(t) for t in rng.integers(1, 128, 13)]
        chunks0 = engine.metrics.prefill_chunks
        short = engine.add_request(
            short_p, SamplingParams(max_new_tokens=12)
        )
        engine.step()   # short admitted + prefilled + first decode
        n_before = len(short.output_token_ids)
        long = engine.add_request(long_p, SamplingParams(max_new_tokens=3))
        engine.step()   # long chunk 1/2; short decodes
        assert long.state is serving.RequestState.PREFILLING
        assert long.output_token_ids == []
        assert len(short.output_token_ids) == n_before + 1
        engine.step()   # long chunk 2/2 (final) + decode
        assert long.state in (
            serving.RequestState.RUNNING, serving.RequestState.FINISHED,
        )
        assert len(long.output_token_ids) >= 1
        assert len(short.output_token_ids) == n_before + 2
        assert engine.metrics.prefill_chunks == chunks0 + 2
        out = {o.request_id: o for o in []}
        done = _drain(engine)
        out.update(done)
        assert out[short.request_id].token_ids == _generate_oracle(
            model, short_p, 12
        )
        assert out[long.request_id].token_ids == _generate_oracle(
            model, long_p, 3
        )

    def test_cow_divergence_never_mutates_shared_block(
        self, model, prefix_engine
    ):
        """Re-serving a prompt of exactly full blocks forks all but the
        last matched block and COPY-ON-WRITES that one (the one-token
        cap makes this request re-write its final slot). The shared
        original's bits must be untouched, and both runs byte-match."""
        engine = prefix_engine
        prompt = [70, 71, 72, 73, 74, 75, 76, 77]    # 2 full blocks
        params = SamplingParams(max_new_tokens=5)
        first = engine.generate([prompt], params)[0]
        match = engine.prefix_cache.lookup(prompt, limit=len(prompt))
        assert match is not None and match.num_shared == 2
        b0, b1 = match.shared_blocks
        snap = [
            (np.asarray(engine.pool.k[li][:, b1]).copy(),
             np.asarray(engine.pool.v[li][:, b1]).copy())
            for li in range(engine.adapter.num_layers)
        ]
        cow0 = engine.metrics.cow_copies
        second = engine.generate([prompt], params)[0]
        assert engine.metrics.cow_copies == cow0 + 1
        assert second.token_ids == first.token_ids
        assert first.token_ids == _generate_oracle(model, prompt, 5)
        for li, (ks, vs) in enumerate(snap):
            assert np.array_equal(
                np.asarray(engine.pool.k[li][:, b1]), ks
            ), f"layer {li}: shared K block mutated by COW divergence"
            assert np.array_equal(
                np.asarray(engine.pool.v[li][:, b1]), vs
            ), f"layer {li}: shared V block mutated by COW divergence"

    def test_reclaimable_cached_blocks_are_not_pressure(
        self, model, prefix_engine
    ):
        """Retained cache blocks count as reclaimable capacity: they
        must not trip the shedding threshold, and health() reports the
        active/reclaimable split."""
        engine = prefix_engine
        engine.generate([[80, 81, 82, 83, 84]],
                        SamplingParams(max_new_tokens=2))
        bm = engine.block_manager
        assert bm.num_used > 0          # retained cache blocks
        h = engine.health()
        assert h["kv_reclaimable_blocks"] == bm.num_used
        assert h["kv_active_utilization"] == 0.0
        assert h["kv_utilization"] > 0.0
        assert h["prefix_cache_blocks"] == len(engine.prefix_cache)
        engine.config.kv_shed_threshold = 0.01
        try:
            # raw utilization is over threshold, active is 0: admission
            # must neither shed nor report overloaded
            ok = engine.add_request([1, 2],
                                    SamplingParams(max_new_tokens=2))
            assert "overloaded" not in engine.health()["flags"]
            out = _drain(engine)
            assert out[ok.request_id].finish_reason == "length"
        finally:
            engine.config.kv_shed_threshold = None

    def test_prefill_analysis_gate(self, prefix_engine):
        """check_decode's counterpart for the new program family: the
        continuation prefill and COW step carry zero host-sync/retrace
        findings, and the trace-only check never moves the compile
        probes."""
        m = prefix_engine.metrics
        before = (m.prefill_ext_compiles, m.cow_compiles)
        report = prefix_engine.check_prefill("error")
        assert not report.by_rule("host-sync")
        assert not report.by_rule("retrace-hazard")
        assert (m.prefill_ext_compiles, m.cow_compiles) == before
        with pytest.raises(ValueError, match="mode"):
            prefix_engine.check_prefill("loud")

    def test_config_validation_and_adapter_gate(self, model):
        with pytest.raises(ValueError, match="prefill_chunk_tokens"):
            EngineConfig(max_model_len=32, prefill_chunk_tokens=0)
        with pytest.raises(ValueError, match="largest prefill bucket"):
            EngineConfig(max_model_len=32, prefill_chunk_tokens=64)
        with pytest.raises(ValueError, match="prefix_cache_blocks"):
            EngineConfig(enable_prefix_cache=True, prefix_cache_blocks=0)
        with pytest.raises(ValueError, match="max_prefill_chunks"):
            EngineConfig(max_prefill_chunks_per_step=0)

        class MinimalAdapter:
            """Duck-typed adapter WITHOUT prefill_ext: fine for plain
            serving, rejected when the features need continuations."""
            import jax.numpy as _jnp

            num_layers, num_kv_heads, head_dim, vocab_size = 1, 1, 4, 8
            weights = {"embed": _jnp.zeros((8, 4), "float32")}

            def prefill(self, *a):
                raise NotImplementedError

            def decode(self, *a):
                raise NotImplementedError

        Engine(MinimalAdapter(), EngineConfig(
            max_batch_slots=1, max_model_len=16, page_size=4,
        ))  # plain config builds fine
        with pytest.raises(TypeError, match="prefill_ext"):
            Engine(MinimalAdapter(), EngineConfig(
                max_batch_slots=1, max_model_len=16, page_size=4,
                enable_prefix_cache=True,
            ))


@pytest.fixture(scope="module")
def spec_engine(model):
    """Shared speculative-decoding engine (K=3 prompt-lookup drafts
    through the VERIFY program). Drains completely between tests, so
    only counters persist; program set: prefill per bucket, ONE verify,
    and the mixed decode variant for sampled slots."""
    return Engine(model, EngineConfig(
        max_batch_slots=4, max_model_len=32, page_size=4,
        num_blocks=48, prefill_buckets=[16, 32], speculate_tokens=3,
        seed=3,
    ))


class TestSpeculativeDecoding:
    """Tentpole acceptance: n-gram drafting + batched verification
    emit byte-identical greedy streams to ``generate`` and to a
    spec-disabled engine, through ONE verify trace — mixed accept
    counts, rejects, EOS-mid-draft, TTL and preemption included."""

    def test_drafter_unit(self):
        from paddle_tpu.serving.speculation import accept_length, propose

        # period-4 cycle: the full-K continuation is preferred over the
        # flush-against-the-tail match that would truncate the draft
        hist = [1, 2, 3, 4] * 4
        assert propose(hist, 6) == [1, 2, 3, 4, 1, 2]
        # disagreeing variants truncate at the common prefix: both
        # occurrences of trailing [9, 5] continue 6, then diverge
        hist = [9, 5, 6, 1, 9, 5, 6, 2, 9, 5]
        assert propose(hist, 3, max_ngram=2) == [6]
        # no repetition to exploit / no budget -> no draft
        assert propose([1, 2, 3, 4, 5], 4) == []
        assert propose([1, 2] * 4, 0) == []
        # near-tail fallback: single short match still drafts
        assert propose([7, 8, 9, 7, 8], 4, max_ngram=2) == [9, 7, 8]
        # acceptance: sticky-reject semantics
        assert accept_length([5, 6, 7], [5, 6, 7]) == 3
        assert accept_length([5, 9, 7], [5, 6, 7]) == 1
        assert accept_length([9, 6, 7], [5, 6, 7]) == 0
        assert accept_length([], [5, 6]) == 0

    def test_mixed_workload_parity_and_compile_probe(
        self, model, small_engine, spec_engine
    ):
        """The 32-request workload with every 4th request SAMPLED:
        greedy outputs byte-match generate() AND the spec-disabled
        engine; compile probes pin one verify trace and zero warm
        retraces."""
        from paddle_tpu.observability import jit_events

        prompts, max_new, _arrivals = _mixed_workload()
        params = [
            SamplingParams(max_new_tokens=k, do_sample=(i % 4 == 3),
                           temperature=0.8, top_k=20)
            for i, k in enumerate(max_new)
        ]
        retr0 = jit_events.retraces_after_warmup()
        outs_spec = spec_engine.generate(prompts, params)
        outs_plain = small_engine.generate(prompts, params)
        oracle_budget = 8   # the plain engine is itself oracle-checked
        for o_s, o_p, p, k, sp in zip(
            outs_spec, outs_plain, prompts, max_new, params
        ):
            if sp.do_sample:
                # sampled slots keep the plain decode path: valid draws
                # (key streams differ between engines, so no byte
                # parity is promised — see docs/serving.md)
                assert len(o_s.token_ids) == k
                assert all(0 <= t < 128 for t in o_s.token_ids)
            else:
                # EVERY greedy request byte-matches the spec-disabled
                # engine; a subsample also hits generate() directly
                # (TestMixedWorkload pins plain == generate on these
                # same length combos — oracle calls are the expensive
                # part of this test, tier-1 budget)
                assert o_s.token_ids == o_p.token_ids, ("spec", p)
                if oracle_budget > 0:
                    oracle_budget -= 1
                    assert o_s.token_ids == _generate_oracle(
                        model, p, k
                    ), ("oracle", p)
        m = spec_engine.metrics
        # ONE verify trace ever; the decode family stays within its
        # usual two static variants (sampled slots use the mixed one;
        # draft-less steps fall back to the greedy-only one); drafting
        # actually happened
        assert m.verify_compiles == 1
        assert m.decode_compiles <= 2
        assert m.prefill_compiles <= 2
        assert m.spec_proposed > 0
        assert m.verify_steps > 0
        assert jit_events.retraces_after_warmup() == retr0
        assert spec_engine.block_manager.num_used == 0

    def test_forced_accept_reject_and_eos_mid_draft(
        self, model, spec_engine, monkeypatch
    ):
        """Deterministic accept/reject edge cases via a controlled
        drafter: an oracle-fed drafter drives all-K acceptance (and an
        EOS inside an accepted draft), an always-wrong drafter drives
        0-accepted — byte parity must hold through all of them."""
        from paddle_tpu.serving import engine as engine_mod

        prompt = [3, 17, 42, 99]
        ref = _generate_oracle(model, prompt, 12)

        def feeding(history, k, **kw):
            done = [int(t) for t in history[len(prompt):]]
            if [int(t) for t in history[:len(prompt)]] == prompt and (
                ref[:len(done)] == done
            ):
                return ref[len(done):len(done) + k]
            return []

        monkeypatch.setattr(engine_mod.speculation, "propose", feeding)
        m = spec_engine.metrics
        v0, a0, p0 = m.verify_steps, m.spec_accepted, m.spec_proposed
        out = spec_engine.generate(
            [prompt], SamplingParams(max_new_tokens=12)
        )[0]
        assert out.token_ids == ref
        # all-K acceptance: 12 tokens in far fewer launches than the
        # plain path's 11 decode steps (K+1 = 4 tokens per launch once
        # drafts flow)
        assert m.verify_steps - v0 <= 5
        assert m.spec_accepted - a0 >= 8
        # EOS inside an accepted draft window: stop exactly where the
        # plain path would — at the token's FIRST occurrence (these
        # random weights repeat: ref[5] also sits earlier in ref) —
        # discarding the accepted remainder
        eos = ref[5]
        out = spec_engine.generate(
            [prompt],
            SamplingParams(max_new_tokens=12, eos_token_id=eos),
        )[0]
        assert out.token_ids == ref[:ref.index(eos) + 1]
        assert out.finish_reason == "stop"

        def wrong(history, k, **kw):
            done = [int(t) for t in history[len(prompt):]]
            if [int(t) for t in history[:len(prompt)]] == prompt and (
                ref[:len(done)] == done
            ):
                return [(t + 1) % 128 for t in ref[len(done):len(done) + k]]
            return []

        monkeypatch.setattr(engine_mod.speculation, "propose", wrong)
        a0, p1 = m.spec_accepted, m.spec_proposed
        out = spec_engine.generate(
            [prompt], SamplingParams(max_new_tokens=12)
        )[0]
        assert out.token_ids == ref          # rejects are invisible
        assert m.spec_accepted == a0         # 0-accepted throughout
        assert m.spec_proposed > p1
        assert spec_engine.block_manager.num_used == 0

    def test_ttl_and_preemption_mid_spec(self, model, spec_engine):
        """TTL expiry finishes a speculating request with "timeout";
        a pool too small for the running set preempts mid-speculation
        and greedy outputs stay byte-identical."""
        running = spec_engine.add_request(
            [6, 7, 6, 7], SamplingParams(max_new_tokens=12)
        )
        spec_engine.step()
        running.deadline = 0.0               # expire mid-flight
        out = _drain(spec_engine)
        assert out[running.request_id].finish_reason == "timeout"
        assert spec_engine.block_manager.num_used == 0

        engine = Engine(model, EngineConfig(
            max_batch_slots=4, max_model_len=32, page_size=4,
            num_blocks=10, prefill_buckets=[32], speculate_tokens=3,
            seed=3,
        ))
        rng = np.random.default_rng(7)
        lens = [int(n) for n in rng.choice([4, 7, 10], 6)]
        prompts = [rng.integers(1, 128, n).tolist() for n in lens]
        max_new = [16 - n for n in lens]
        outs = engine.generate(
            prompts,
            [SamplingParams(max_new_tokens=k) for k in max_new],
        )
        assert engine.metrics.preemptions >= 1
        for o, p, k in zip(outs, prompts, max_new):
            assert o.token_ids == _generate_oracle(model, p, k)
        assert engine.block_manager.num_used == 0

    def test_spec_observability_and_health(self, spec_engine):
        """spec_* counters reach the registry view (histogram
        included) and health() reports the accept rate."""
        from paddle_tpu.observability import get_registry

        # draft here: under xdist the tests that drafted on this shared
        # engine may have run on another worker, or not yet
        spec_engine.generate([[7] * 12], SamplingParams(max_new_tokens=8))
        m = spec_engine.metrics
        assert m.spec_proposed > 0
        assert m.spec_accept_hist()
        rate = spec_engine.health()["spec_accept_rate"]
        assert rate is not None and 0.0 <= rate <= 1.0
        text = get_registry().render_prometheus()
        for needle in (
            "paddle_tpu_serving_spec_proposed_total",
            "paddle_tpu_serving_spec_accepted_total",
            "paddle_tpu_serving_verify_steps_total",
            "paddle_tpu_serving_spec_accept_length_bucket",
            "paddle_tpu_serving_spec_accept_length_count",
        ):
            assert needle in text, needle

    def test_check_verify_gate(self, small_engine, spec_engine):
        """The analysis gate for the verify program: zero host-sync /
        retrace findings, trace-only (probes unmoved), and clear
        errors for misuse."""
        m = spec_engine.metrics
        before = (m.verify_compiles, m.decode_compiles)
        report = spec_engine.check_verify("error")
        assert not report.by_rule("host-sync")
        assert not report.by_rule("retrace-hazard")
        assert (m.verify_compiles, m.decode_compiles) == before
        with pytest.raises(ValueError, match="mode"):
            spec_engine.check_verify("loud")
        with pytest.raises(RuntimeError, match="speculate_tokens"):
            small_engine.check_verify()

    def test_spec_config_validation_and_adapter_gate(self, model):
        with pytest.raises(ValueError, match="speculate_tokens"):
            EngineConfig(max_model_len=32, speculate_tokens=0)
        with pytest.raises(ValueError, match="speculate_tokens"):
            EngineConfig(max_model_len=32, speculate_tokens=32)
        with pytest.raises(ValueError, match="speculate_ngram"):
            EngineConfig(max_model_len=32, speculate_ngram=0)

        class MinimalAdapter:
            """Duck-typed adapter without the optional entry points."""
            import jax.numpy as _jnp

            num_layers, num_kv_heads, head_dim, vocab_size = 1, 1, 4, 8
            weights = {"embed": _jnp.zeros((8, 4), "float32")}

            def prefill(self, *a):
                raise NotImplementedError

            def decode(self, *a):
                raise NotImplementedError

        # ONE clear TypeError naming the missing method AND the flag
        with pytest.raises(TypeError, match="verify") as ei:
            Engine(MinimalAdapter(), EngineConfig(
                max_batch_slots=1, max_model_len=16, page_size=4,
                speculate_tokens=2,
            ))
        assert "speculate_tokens" in str(ei.value)
        with pytest.raises(TypeError, match="prefill_ext") as ei:
            Engine(MinimalAdapter(), EngineConfig(
                max_batch_slots=1, max_model_len=16, page_size=4,
                enable_prefix_cache=True,
            ))
        assert "enable_prefix_cache" in str(ei.value)


class TestPrefixCacheUnit:
    """Host-only BlockManager + PrefixCache invariants: refcount safety
    under sharing, chain-keyed matching, LRU eviction returning blocks
    to the free list."""

    def test_register_retains_and_eviction_releases(self):
        from paddle_tpu.serving import BlockManager, PrefixCache

        bm = BlockManager(8, 4)
        pc = PrefixCache(bm, capacity_blocks=2)
        blocks = bm.allocate(3)
        assert bm.high_water == 3
        pc.register(list(range(12)), blocks, 12)
        # budget 2: the tail entry was evicted leaf-first immediately
        assert len(pc) == 2
        bm.free(blocks)   # the owning request releases
        # evicted tail block went back to the free list; the two cached
        # blocks are retained by the cache's own reference
        assert bm.num_used == 2
        assert pc.reclaimable_blocks() == 2
        assert pc.reclaim(2) == 2
        assert bm.num_used == 0 and bm.num_free == 8
        # refcount discipline survived the whole dance
        with pytest.raises(RuntimeError, match="double free"):
            bm.free([blocks[0]])
        with pytest.raises(RuntimeError, match="fork of free"):
            bm.fork([blocks[0]])

    def test_lookup_chain_cap_and_cow(self):
        from paddle_tpu.serving import BlockManager, PrefixCache

        bm = BlockManager(8, 4)
        pc = PrefixCache(bm, capacity_blocks=8)
        blocks = bm.allocate(2)
        prompt = list(range(8))
        pc.register(prompt, blocks, 8)
        # full-width match, block-aligned cap: both blocks forkable
        m = pc.lookup(prompt, limit=8)
        assert m.cache_len == 8
        assert m.shared_blocks == blocks and m.cow_src is None
        # the one-token-to-prefill cap cuts into the last block: only
        # the first is forked, the second becomes the COW source
        m = pc.lookup(prompt, limit=7)
        assert m.cache_len == 7
        assert m.shared_blocks == blocks[:1]
        assert m.cow_src == blocks[1]
        # divergent second block: chain stops after one block
        m = pc.lookup(prompt[:4] + [99, 98, 97, 96], limit=7)
        assert m.cache_len == 4 and m.shared_blocks == blocks[:1]
        # nothing shared / prompt shorter than a block: miss
        assert pc.lookup(list(range(100, 108)), limit=7) is None
        assert pc.lookup(prompt[:3], limit=2) is None

    def test_reclaim_skips_blocks_live_requests_hold(self):
        from paddle_tpu.serving import BlockManager, PrefixCache

        bm = BlockManager(8, 4)
        pc = PrefixCache(bm, capacity_blocks=8)
        blocks = bm.allocate(2)
        pc.register(list(range(8)), blocks, 8)
        # a second request forks the blocks (still reading them)
        bm.fork(blocks)
        bm.free(blocks)  # first owner gone; cache ref + reader remain
        assert pc.reclaimable_blocks() == 0
        assert pc.reclaim(2) == 0        # nothing reclaimable
        bm.free(blocks)  # reader done
        assert pc.reclaimable_blocks() == 2
        # protect the chain ROOT: the unprotected leaf frees, then the
        # root survives as the new (protected) leaf
        assert pc.reclaim(5, protect={blocks[0]}) == 1
        assert bm.ref_count(blocks[0]) == 1
        assert bm.ref_count(blocks[1]) == 0


class TestKVPoolRebind:
    def test_rebind_validates_layout(self):
        import jax.numpy as jnp

        from paddle_tpu.serving import KVPool

        pool = KVPool(2, 2, 4, 4, 8)
        pool.rebind(pool.k, pool.v)   # identity rebind is fine
        with pytest.raises(ValueError, match="expected 2 k/v layers"):
            pool.rebind(pool.k[:1], pool.v[:1])
        bad = tuple(jnp.zeros((2, 4, 4, 4), "float32") for _ in range(2))
        with pytest.raises(ValueError) as ei:
            pool.rebind(bad, pool.v)
        # both shapes named in the error
        assert "(2, 4, 4, 4)" in str(ei.value)
        assert "(2, 4, 4, 8)" in str(ei.value)
        wrong_dtype = tuple(
            jnp.zeros((2, 4, 4, 8), "bfloat16") for _ in range(2)
        )
        with pytest.raises(ValueError, match="dtype"):
            pool.rebind(wrong_dtype, pool.v)


class TestKernelPathsAndInt8KV:
    """EngineConfig(decode_kernel=) + EngineConfig(kv_cache_dtype=):
    kernel-path selection with counted (never fatal) degradation, and
    the int8 KV byte-budget/tolerance contract (docs/kernels.md)."""

    PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [2, 4, 6, 8, 10, 12]]
    SP = SamplingParams(max_new_tokens=6, eos_token_id=None)

    def _cfg(self, **kw):
        return EngineConfig(
            max_batch_slots=4, max_model_len=32, page_size=4, seed=3,
            **kw,
        )

    def test_decode_kernel_pallas_degrades_counted(self, model,
                                                   small_engine):
        import warnings

        from paddle_tpu.kernels.pallas._compat import fallbacks_total

        base = small_engine.generate(self.PROMPTS, self.SP)
        before = fallbacks_total()
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            eng = Engine(model, self._cfg(decode_kernel="pallas"))
            outs = eng.generate(self.PROMPTS, self.SP)
        # off-TPU the explicit pallas request degrades to the XLA
        # fallback: same bytes out, counted + warned, never raised
        assert [o.token_ids for o in outs] == [
            o.token_ids for o in base
        ]
        assert fallbacks_total() > before
        assert any("degraded" in str(x.message) for x in w)
        h = eng.health()
        assert h["decode_kernel"] == "pallas"
        assert h["kv_cache_dtype"] == "float32"

    def test_decode_kernel_interpret_parity(self, model, small_engine):
        # FLAGS_pallas_interpret pins the interpreted kernel off-TPU:
        # the real kernel body runs (no degradation) and greedy decode
        # agrees with the XLA path on this model
        from paddle_tpu.kernels.pallas._compat import fallbacks_total

        base = small_engine.generate(self.PROMPTS, self.SP)
        before = fallbacks_total()
        paddle.set_flags({"FLAGS_pallas_interpret": True})
        try:
            eng = Engine(model, self._cfg(decode_kernel="pallas"))
            outs = eng.generate(self.PROMPTS, self.SP)
        finally:
            paddle.set_flags({"FLAGS_pallas_interpret": False})
        assert fallbacks_total() == before
        assert [o.token_ids for o in outs] == [
            o.token_ids for o in base
        ]

    def test_decode_kernel_needs_adapter_knob(self, model):
        class Opaque:
            """Adapter surface WITHOUT the decode_kernel knob."""
            num_layers = num_kv_heads = head_dim = vocab_size = 1
            weights = {}
            import numpy as _np
            dtype = _np.float32

            def prefill(self, *a):
                raise NotImplementedError

            def decode(self, *a):
                raise NotImplementedError

        class NoKnob(Opaque):
            __slots__ = ()  # attribute writes rejected

        with pytest.raises(TypeError, match="decode_kernel"):
            Engine(NoKnob(), self._cfg(decode_kernel="pallas"))
        with pytest.raises(ValueError, match="decode_kernel"):
            self._cfg(decode_kernel="cuda")

    def test_int8_kv_halves_bytes_and_generates(self, model,
                                                small_engine):
        eng = Engine(model, self._cfg(kv_cache_dtype="int8"))
        # byte budget: the int8 pool must store a token in at most HALF
        # the bytes of the float pool (fp32 here: ~3.8x)
        assert eng.pool.bytes_per_token() <= (
            0.5 * small_engine.pool.bytes_per_token()
        )
        h = eng.health()
        assert h["kv_cache_dtype"] == "int8"
        assert h["kv_bytes_per_token"] == eng.pool.bytes_per_token()
        outs = eng.generate(self.PROMPTS, self.SP)
        # tolerance contract, not byte parity: generation completes to
        # length with in-vocab tokens (docs/serving.md caveats)
        for o in outs:
            assert o.finish_reason == "length"
            assert len(o.token_ids) == 6
            assert all(
                0 <= t < model.config.vocab_size for t in o.token_ids
            )

    def test_int8_pool_rebind_validates(self):
        import jax.numpy as jnp

        from paddle_tpu.serving import KVPool

        pool = KVPool(2, 2, 4, 4, 8, quant_dtype="int8")
        assert pool.bytes_per_token() == 2 * 2 * 2 * (8 + 4)
        pool.rebind(pool.k, pool.v)  # identity rebind fine
        with pytest.raises(ValueError, match="pages, scales"):
            pool.rebind(
                tuple(p for p, _ in pool.k), pool.v
            )
        bad_scale = tuple(
            (p, jnp.zeros((2, 4, 4), "bfloat16")) for p, _ in pool.k
        )
        with pytest.raises(ValueError, match="dtype"):
            pool.rebind(bad_scale, pool.v)
        with pytest.raises(ValueError, match="quant_dtype"):
            KVPool(2, 2, 4, 4, 8, quant_dtype="int4")

    def test_mixed_workload_parity_pallas_vs_xla(self, model):
        # the 32-request acceptance workload through a decode_kernel=
        # "pallas" engine vs the byte-reference "xla" engine: off-TPU
        # the pallas request degrades to the same fallback program, so
        # the tolerance contract collapses to byte parity — what this
        # asserts, along with the single-compile invariant holding
        # under the new config axis
        prompts, max_new, _ = _mixed_workload(32)
        outs = {}
        for dk in ("xla", "pallas"):
            eng = Engine(model, EngineConfig(
                max_batch_slots=4, max_model_len=32, page_size=4,
                num_blocks=16, prefill_buckets=[16, 32],
                decode_kernel=dk,
            ))
            res = eng.generate(
                prompts,
                [SamplingParams(max_new_tokens=k) for k in max_new],
            )
            outs[dk] = [o.token_ids for o in res]
            assert eng.metrics.decode_compiles == 1
        assert outs["pallas"] == outs["xla"]

    @pytest.mark.slow
    def test_warm_restart_zero_traces_with_kernel_flags(self, model,
                                                        tmp_path):
        # decode_kernel/kv_cache_dtype join the service key + program
        # signatures: a warm restart replays the full program set with
        # zero fresh traces and zero warm-retrace alarms
        from paddle_tpu.observability import jit_events

        cfg = dict(
            max_batch_slots=2, max_model_len=32, page_size=4, seed=3,
            decode_kernel="pallas", kv_cache_dtype="int8",
            compile_cache=str(tmp_path / "cc"),
        )
        cold = Engine(model, EngineConfig(**cfg))
        out1 = cold.generate(self.PROMPTS[:2], self.SP)
        warm = Engine(model, EngineConfig(**cfg))
        out2 = warm.generate(self.PROMPTS[:2], self.SP)
        m = warm.metrics
        assert (m.prefill_compiles, m.decode_compiles) == (0, 0)
        assert [o.token_ids for o in out1] == [
            o.token_ids for o in out2
        ]
        assert jit_events.retraces_after_warmup() == 0
