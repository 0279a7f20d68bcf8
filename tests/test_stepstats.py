"""Serving step observatory (observability/stepstats.py + engine wiring).

The acceptance criteria of the observatory, asserted directly:

  * the goodput ledger reconciles EXACTLY with the engine's timeline
    counters under adversarial mixes — a forced 0-accept drafter,
    forced recompute preemption, and a cross-engine migration:

        useful + wasted_preempt + wasted_migration + wasted_aborted
               == prefill_tokens + decode_tokens
        wasted_spec == spec_proposed - spec_accepted

  * greedy outputs are byte-identical with the observatory on or off,
    and a warm engine's compile probes do not move with it on;
  * the ``obs.stepstats`` fault site disables the sampler (one
    RuntimeWarning) without perturbing the step that carried it;
  * the collector view is weakref-held: a dropped sampler disappears
    from the exposition;
  * the dump/top CLI render the step-sample ring and the live tables.
"""
import gc
import io

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability.metrics import MetricsRegistry
from paddle_tpu.observability.stepstats import (
    StepStats,
    flops_per_token,
    register_stepstats_view,
)
from paddle_tpu.resilience import FaultSpec, faults
from paddle_tpu.serving import Engine, EngineConfig, SamplingParams


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny())


def _generate_oracle(model, prompt, max_new):
    ids = paddle.to_tensor(np.array([prompt], dtype="int64"))
    out = model.generate(ids, max_new_tokens=max_new)
    return out.numpy()[0, len(prompt):].tolist()


def _cfg(**kw):
    base = dict(
        max_batch_slots=4, max_model_len=32, page_size=4,
        prefill_buckets=[32],
    )
    base.update(kw)
    return EngineConfig(**base)


def _reconciles(engine):
    """The exact ledger/timeline reconciliation identity."""
    st, m = engine.stepstats, engine.metrics
    assert (
        st.useful_tokens + st.wasted_preempt_tokens
        + st.wasted_migration_tokens + st.wasted_aborted_tokens
        == m.prefill_tokens + m.decode_tokens
    ), (st.ledger(), m.prefill_tokens, m.decode_tokens)
    assert st.wasted_spec_tokens == m.spec_proposed - m.spec_accepted


class TestStepStatsUnit:
    """Sampler arithmetic with no engine (backend pinned to "cpu" so
    no jax import happens on this path)."""

    def _fake_adapter(self, n_params=100):
        class A:
            weights = {"w": np.zeros(n_params, dtype="float32")}
        return A()

    def test_flops_per_token_palm_convention(self):
        assert flops_per_token(self._fake_adapter(50)) == 100.0
        assert flops_per_token(object()) is None

    def test_ledger_classes_and_goodput(self):
        st = StepStats()
        assert st.goodput_fraction() == 1.0  # idle engine wastes nothing
        st.begin_step()
        st.note_prefill(10)                      # first-time: useful
        st.note_prefill(4, cause="preempt")
        st.note_prefill(3, cause="migration")
        st.note_decode(5)
        st.note_spec_reject(2)
        st.end_step(occupancy=0.5, queue_depth=1)
        assert st.ledger() == {
            "useful": 15, "spec_reject": 2, "preempt_recompute": 4,
            "migration_reprefill": 3, "aborted": 0,
        }
        assert st.goodput_fraction() == 15 / 24
        st.note_abort(5)                         # reclassify, not add
        assert st.useful_tokens == 10
        assert st.wasted_aborted_tokens == 5
        assert st.goodput_fraction() == 10 / 24

    def test_restored_cause_counts_useful(self):
        """A host-spill restore (serving/spill.py) makes the residual
        prefill real forward progress: cause="restored" lands in
        useful, not preempt_recompute."""
        st = StepStats()
        st.begin_step()
        st.note_prefill(6, cause="restored")
        st.note_prefill(4, cause="preempt")
        st.end_step(occupancy=0.5)
        assert st.useful_tokens == 6
        assert st.wasted_preempt_tokens == 4

    def test_idle_step_skipped_but_gauges_refresh(self):
        st = StepStats()
        st.begin_step()
        assert st.end_step(occupancy=0.0, queue_depth=0) is None
        assert not st.samples
        st.begin_step()
        st.note_decode(1)
        assert st.end_step(occupancy=0.25, queue_depth=2) is not None
        assert st.last_occupancy == 0.25 and st.last_queue_depth == 2

    def test_host_overhead_split_and_sample_shape(self):
        st = StepStats()
        st.begin_step()
        st.record_launch("prefill", 0.010)
        st.record_launch("decode", 0.005)
        st.note_decode(3)
        s = st.end_step(
            occupancy=0.75, queue_depth=0,
            kv_free_blocks=5, kv_reclaimable_blocks=2,
        )
        assert s["wall_ms"] >= 0
        # host overhead = step wall minus the launch walls, floored at 0
        assert s["host_ms"] == pytest.approx(
            max(s["wall_ms"] - 15.0, 0.0), abs=1e-6
        )
        assert s["launches"] == [("prefill", 10.0), ("decode", 5.0)]
        assert s["tokens"] == 3
        assert s["kv_headroom_blocks"] == 7
        assert sorted(st.digests) == ["decode", "host", "prefill"]

    def test_mfu_window_deterministic(self):
        st = StepStats(
            adapter=self._fake_adapter(100),   # 200 flops/token
            tp_degree=2, peak_flops_per_chip=100.0,
        )
        assert st.mfu() is None                # no samples yet
        st.begin_step()
        st.note_decode(10)
        st.end_step(occupancy=1.0)
        t0 = st.samples[0]["ts"]
        # 10 tok * 200 flops / 5 s / (100 * 2 chips) = 2.0
        assert st.mfu(now=t0 + 5.0) == pytest.approx(2.0)

    def test_ring_bound_and_validation(self):
        st = StepStats(ring=4)
        for _ in range(10):
            st.begin_step()
            st.note_decode(1)
            st.end_step(occupancy=1.0)
        assert len(st.samples) == 4
        with pytest.raises(ValueError, match="ring"):
            StepStats(ring=0)
        with pytest.raises(ValueError, match="stepstats_ring"):
            EngineConfig(max_model_len=32, stepstats_ring=0)

    def test_view_weakref_unregisters_on_drop(self):
        reg = MetricsRegistry()
        st = StepStats()
        st.begin_step()
        st.note_decode(2)
        st.end_step(occupancy=0.5)
        register_stepstats_view(st, "t0", registry=reg)
        text = reg.render_prometheus()
        assert 'paddle_tpu_serving_goodput_tokens_total{'
        assert 'class="useful",engine="t0"' in text
        del st
        gc.collect()
        assert "engine=\"t0\"" not in reg.render_prometheus()


class TestEngineIntegration:
    def test_attribution_parity_and_exposition(self, model):
        """Happy path: per-program digests populate, health() carries
        the summary + headroom, the five families render, and the
        ledger reconciles with goodput 1.0 (nothing was wasted)."""
        engine = Engine(model, _cfg())
        prompts = [[3, 1, 4, 1], [2, 7, 1, 8, 2], [9, 9]]
        outs = engine.generate(
            prompts, [SamplingParams(max_new_tokens=6)] * 3
        )
        for o, p in zip(outs, prompts):
            assert o.token_ids == _generate_oracle(model, p, 6)
        st = engine.stepstats
        _reconciles(engine)
        assert st.goodput_fraction() == 1.0
        assert {"prefill", "decode", "host"} <= set(st.digests)
        assert len(st.samples) >= 1
        h = engine.health()
        assert h["stepstats"]["tokens"]["useful"] == st.useful_tokens
        assert h["kv_headroom_blocks"] == (
            engine.block_manager.num_free
            + h["kv_reclaimable_blocks"]
        )
        assert h["kv_headroom_bytes_per_chip"] > 0
        text = obs_metrics.get_registry().render_prometheus()
        eid = f'engine="{engine.engine_id}"'
        for family in (
            "paddle_tpu_serving_step_seconds",
            "paddle_tpu_serving_occupancy",
            "paddle_tpu_serving_goodput_fraction",
            "paddle_tpu_serving_goodput_tokens_total",
            "paddle_tpu_serving_kv_headroom_blocks",
        ):
            assert any(
                line.startswith(family) and eid in line
                for line in text.splitlines()
            ), family
        # the CPU mesh has no published peak: no utilization is made up
        assert st.mfu() is None
        assert not any(
            line.startswith("paddle_tpu_serving_mfu") and eid in line
            for line in text.splitlines()
        )

    def test_goodput_spec_reject_reconciles(self, model, monkeypatch):
        """A forced always-wrong drafter: every proposed token is
        verify-computed and rejected — the ledger must charge exactly
        spec_proposed - spec_accepted to spec_reject, byte parity
        intact."""
        from paddle_tpu.serving import engine as engine_mod

        engine = Engine(model, _cfg(
            num_blocks=48, prefill_buckets=[16, 32], speculate_tokens=3,
        ))
        prompt = [3, 17, 42, 99]
        ref = _generate_oracle(model, prompt, 12)

        def wrong(history, k, **kw):
            done = [int(t) for t in history[len(prompt):]]
            if [int(t) for t in history[:len(prompt)]] == prompt and (
                ref[:len(done)] == done
            ):
                return [
                    (t + 1) % 128 for t in ref[len(done):len(done) + k]
                ]
            return []

        monkeypatch.setattr(engine_mod.speculation, "propose", wrong)
        out = engine.generate(
            [prompt], SamplingParams(max_new_tokens=12)
        )[0]
        assert out.token_ids == ref
        st, m = engine.stepstats, engine.metrics
        assert m.spec_accepted == 0
        assert st.wasted_spec_tokens == m.spec_proposed > 0
        _reconciles(engine)
        assert st.goodput_fraction() < 1.0

    def test_goodput_preemption_reconciles(self, model):
        """A pool too small for the running set forces recompute
        preemption; the re-prefilled context is charged to
        preempt_recompute and the identity still closes exactly."""
        rng = np.random.default_rng(7)
        lens = [int(n) for n in rng.choice([4, 7, 10], 6)]
        prompts = [rng.integers(1, 128, n).tolist() for n in lens]
        max_new = [16 - n for n in lens]
        engine = Engine(model, _cfg(num_blocks=10))
        outs = engine.generate(
            prompts, [SamplingParams(max_new_tokens=k) for k in max_new]
        )
        assert engine.metrics.preemptions >= 1
        for o, p, k in zip(outs, prompts, max_new):
            assert o.token_ids == _generate_oracle(model, p, k)
        st = engine.stepstats
        assert st.wasted_preempt_tokens > 0
        assert st.wasted_migration_tokens == 0
        _reconciles(engine)
        assert st.goodput_fraction() < 1.0

    def test_goodput_spill_restore_reconciles(self, model):
        """The SAME thrash mix as the preemption test, but with the
        host spill tier on: every preemption resumes through a restore
        instead of a recompute, so preempt_recompute collapses to zero
        while the identity still closes exactly and greedy outputs
        stay byte-identical to the oracle."""
        rng = np.random.default_rng(7)
        lens = [int(n) for n in rng.choice([4, 7, 10], 6)]
        prompts = [rng.integers(1, 128, n).tolist() for n in lens]
        max_new = [16 - n for n in lens]
        engine = Engine(model, _cfg(
            num_blocks=10, host_spill_bytes=64 * 1024 * 1024,
        ))
        outs = engine.generate(
            prompts, [SamplingParams(max_new_tokens=k) for k in max_new]
        )
        assert engine.metrics.preemptions >= 1
        for o, p, k in zip(outs, prompts, max_new):
            assert o.token_ids == _generate_oracle(model, p, k)
        st = engine.stepstats
        tier = engine.spill.stats()
        assert tier["restored_blocks"]["request"] > 0
        # restores replaced every recompute the thrash would have cost
        assert st.wasted_preempt_tokens == 0
        assert st.wasted_migration_tokens == 0
        _reconciles(engine)

    def test_goodput_migration_reconciles(self, model):
        """release() on one engine + resume() on another (the fleet
        shrink/failover path): the destination's re-prefill over
        prompt + output[:-1] is ALL migration waste — its ledger
        charges exactly its prefill_tokens to migration_reprefill."""
        e1 = Engine(model, _cfg())
        e2 = Engine(model, _cfg())
        prompt = [3, 17, 42, 99]
        ref = _generate_oracle(model, prompt, 10)
        req = e1.add_request(prompt, SamplingParams(max_new_tokens=10))
        for _ in range(4):
            e1.step()
        n_before = len(req.output_token_ids)
        assert 1 <= n_before < 10
        assert e1.release(req.request_id) is req
        e2.resume(req)
        while e2.has_unfinished():
            e2.step()
        assert req.output_token_ids == ref
        st2, m2 = e2.stepstats, e2.metrics
        # the whole re-prefill (prompt + carried output minus the
        # last token, which the next decode re-emits) is waste
        assert st2.wasted_migration_tokens == m2.prefill_tokens
        assert m2.prefill_tokens == len(prompt) + n_before - 1
        assert st2.wasted_preempt_tokens == 0
        _reconciles(e2)
        # the source engine wasted nothing: its prefill was first-time
        _reconciles(e1)
        assert e1.stepstats.wasted_migration_tokens == 0

    def test_abort_reclassifies_emitted_tokens(self, model):
        engine = Engine(model, _cfg())
        req = engine.add_request(
            [5, 6, 7], SamplingParams(max_new_tokens=20)
        )
        for _ in range(5):
            engine.step()
        n = len(req.output_token_ids)
        assert n >= 1
        st = engine.stepstats
        useful_before = st.useful_tokens
        engine.abort(req.request_id)
        engine.step()   # deliver the aborted RequestOutput
        assert st.wasted_aborted_tokens == n
        assert st.useful_tokens == useful_before - n
        _reconciles(engine)

    def test_parity_and_zero_new_compiles_with_observatory(self, model):
        """Stepstats on vs off: byte-identical greedy outputs; and a
        warm engine's traced-body compile probes do not move across a
        second pass with the observatory active."""
        prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 5]]
        params = [SamplingParams(max_new_tokens=6)] * 3
        on = Engine(model, _cfg())
        off = Engine(model, _cfg(stepstats=False))
        assert off.stepstats is None
        outs_on = on.generate(prompts, params)
        m = on.metrics
        probes = (
            m.prefill_compiles, m.prefill_ext_compiles,
            m.decode_compiles, m.verify_compiles, m.cow_compiles,
        )
        outs_on2 = on.generate(prompts, params)
        assert (
            m.prefill_compiles, m.prefill_ext_compiles,
            m.decode_compiles, m.verify_compiles, m.cow_compiles,
        ) == probes
        outs_off = off.generate(prompts, params)
        ids = lambda outs: [o.token_ids for o in outs]  # noqa: E731
        assert ids(outs_on) == ids(outs_off) == ids(outs_on2)
        # the off engine exports no stepstats view and pays no ledger
        assert off.health()["stepstats"] is None

    def test_fault_site_disables_sampler_not_step(self, model):
        engine = Engine(model, _cfg())
        prompt = [3, 17, 42]
        ref = _generate_oracle(model, prompt, 6)
        spec = FaultSpec(RuntimeError("boom"), at=1)
        with faults.inject({"obs.stepstats": spec}) as inj:
            with pytest.warns(RuntimeWarning, match="step observatory"):
                out = engine.generate(
                    [prompt], SamplingParams(max_new_tokens=6)
                )[0]
        assert inj.fired["obs.stepstats"] == 1
        assert out.token_ids == ref          # the step was unperturbed
        assert engine.stepstats is None      # sampler self-disabled
        # and the engine keeps serving without the observatory
        out2 = engine.generate(
            [prompt], SamplingParams(max_new_tokens=6)
        )[0]
        assert out2.token_ids == ref


class TestCLI:
    def test_dump_renders_step_samples_and_goodput(self):
        """Golden-output check on the dump renderer's stepstats
        sections (fixed payload, exact expected text)."""
        from paddle_tpu.observability.__main__ import (
            _fmt_ts, _render_dump,
        )

        payload = {
            "reason": "test", "pid": 7, "ts": 0.0,
            "step_samples": [{
                "ts": 0.0, "engine": 3, "wall_ms": 12.5, "host_ms": 2.5,
                "launches": [["prefill", 6.0], ["decode", 4.0]],
                "tokens": 9, "occupancy": 0.75, "queue_depth": 2,
                "kv_free_blocks": 5, "kv_reclaimable_blocks": 1,
                "kv_headroom_blocks": 6,
            }],
            "metrics": {
                "paddle_tpu_serving_goodput_tokens_total"
                "{class=useful,engine=3}": 30,
                "paddle_tpu_serving_goodput_tokens_total"
                "{class=spec_reject,engine=3}": 6,
                "paddle_tpu_serving_goodput_fraction{engine=3}": 30 / 36,
                "paddle_tpu_serving_mfu{engine=3}": 0.0125,
            },
        }
        out = io.StringIO()
        _render_dump(payload, out)
        t = _fmt_ts(0.0)
        text = out.getvalue()
        assert (
            f"  {t} eng=3 wall=12.5ms host=2.5ms occ=0.75 q=2 tok=9"
            " kv_headroom=6 [prefill=6.0ms decode=4.0ms]\n"
        ) in text
        assert "-- goodput ledger (tokens) " in text
        assert "  spec_reject=6 useful=30\n" in text
        assert "  goodput[engine=3] = 0.8333\n" in text
        assert "  mfu[engine=3] = 0.0125\n" in text

    def test_top_renders_live_scrape(self, model, capsys):
        """``observability top`` against a real scrape endpoint over a
        just-driven engine: the per-program table and the utilization
        lines render off /metrics."""
        from paddle_tpu.observability import start_scrape_server
        from paddle_tpu.observability.__main__ import main

        engine = Engine(model, _cfg())
        engine.generate(
            [[4, 5, 6]], [SamplingParams(max_new_tokens=4)]
        )
        srv = start_scrape_server(port=0)
        try:
            rc = main(["top", "--url", srv.url])
        finally:
            srv.close()
        assert rc == 0
        out = capsys.readouterr().out
        eid = str(engine.engine_id)
        assert f"engine {eid}" in out
        for prog in ("prefill", "decode", "host"):
            assert prog in out
        assert "occupancy=" in out and "goodput=" in out
        assert "mfu=" not in out   # no peak off-TPU, so no gauge
        assert f"kv headroom: engine {eid}" in out
