"""One-call hybrid-parallel API: dist.parallelize.

ref contract: auto_parallel/intermediate/parallelize.py:51 (config-driven
DP/MP/PP composition) + the hybrid_strategy integration tests that run a
tiny Llama under every parallelism combo
(test/auto_parallel/hybrid_strategy/semi_auto_llama.py). Oracle: the
single-device model — every parallel config must produce the same loss.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def _cfg(**kw):
    base = dict(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=4, num_attention_heads=4,
    )
    base.update(kw)
    return LlamaConfig.tiny(**base)


def _data(cfg, batch=8, seq=12, seed=0):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq)
    ).astype("int64")


def _ref_loss(cfg, ids, steps=1, lr=1e-2):
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(
        learning_rate=lr, parameters=model.parameters()
    )
    losses = []
    for _ in range(steps):
        _, loss = model(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


class TestParallelizeGSPMD:
    def test_dp_tp_zero_loss_parity(self):
        cfg = _cfg()
        ids = _data(cfg)
        ref = _ref_loss(cfg, ids, steps=3)

        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=model.parameters()
        )
        model, opt = dist.parallelize(
            model, opt,
            config={
                "dp_degree": 2, "mp_degree": 4,
                "dp_config": {"sharding_level": 1},
                "mp_config": {"parallelize_plan": "auto"},
            },
        )
        losses = []
        for _ in range(3):
            _, loss = model(
                paddle.to_tensor(ids), labels=paddle.to_tensor(ids)
            )
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        np.testing.assert_allclose(losses, ref, rtol=2e-4, atol=2e-5)

    def test_tp_params_actually_sharded(self):
        cfg = _cfg()
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        model, _ = dist.parallelize(
            model, None, config={"mp_degree": 8}
        )
        q = dict(model.named_parameters())[
            "llama.layers.0.self_attn.q_proj.weight"
        ]
        assert q._dist_meta is not None
        assert any(p.is_shard() for p in q._dist_meta.placements)

    def test_trainstep_compatible(self):
        cfg = _cfg()
        ids = _data(cfg)
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=model.parameters()
        )
        model, opt = dist.parallelize(
            model, opt,
            config={"dp_degree": 2, "mp_degree": 4,
                    "dp_config": {"sharding_level": 2}},
        )
        step = paddle.jit.TrainStep(
            model, lambda m, x: m(x, labels=x)[1], opt, donate=False
        )
        l0 = float(step(paddle.to_tensor(ids)).numpy())
        l1 = float(step(paddle.to_tensor(ids)).numpy())
        assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0

    def test_flash_kernel_runs_per_shard_under_dp_tp(self, monkeypatch):
        """Mosaic kernels have no SPMD rule, so under dist.parallelize
        the flash kernel must run per shard (shard_map over the dp/tp
        axes the wrapper declares), with the same losses as the unsharded
        math path. Donation stays on, as on the chip."""
        from paddle_tpu.kernels.pallas import flash_attention as fa

        cfg = _cfg(max_position_embeddings=128)
        ids = _data(cfg, batch=4, seq=128)
        seen, current = [], fa.current_spmd_axes

        def spy():
            seen.append(current())
            return seen[-1]

        monkeypatch.setattr(fa, "current_spmd_axes", spy)

        def losses(parallel, flash_min_seq):
            paddle.set_flags(
                {"FLAGS_flash_attention_min_seq": flash_min_seq})
            paddle.seed(0)
            model = LlamaForCausalLM(cfg)
            opt = paddle.optimizer.AdamW(
                learning_rate=1e-2, parameters=model.parameters()
            )
            if parallel:
                model, opt = dist.parallelize(
                    model, opt, config={"dp_degree": 2, "mp_degree": 2})
            step = paddle.jit.TrainStep(
                model, lambda m, x: m(x, labels=x)[1], opt)
            return [float(step(paddle.to_tensor(ids)).numpy())
                    for _ in range(2)]

        try:
            ref = losses(False, 4096)          # math sdpa, one device
            got = losses(True, 128)            # flash kernel, dp2 x tp2
        finally:
            paddle.set_flags({"FLAGS_flash_attention_min_seq": 2048})
        np.testing.assert_allclose(got, ref, rtol=2e-4)
        # the interpreter would pass without it: the kernel really was
        # told the mesh axes (and wrapped itself in shard_map over them)
        assert seen and all(
            a is not None and a[1:] == ("dp", "tp") for a in seen)

    def test_bad_degrees_raise(self):
        cfg = _cfg()
        model = LlamaForCausalLM(cfg)
        with pytest.raises(ValueError):
            dist.parallelize(model, None, config={"dp_degree": 16})


class TestParallelizePipeline:
    @pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
    def test_pp_loss_matches_single_device(self, schedule):
        cfg = _cfg()
        ids = _data(cfg)
        paddle.seed(0)
        ref_model = LlamaForCausalLM(cfg)
        _, ref_loss = ref_model(
            paddle.to_tensor(ids), labels=paddle.to_tensor(ids)
        )

        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        pmodel, _ = dist.parallelize(
            model, None,
            config={"pp_degree": 4,
                    "pp_config": {"schedule": schedule,
                                  "micro_batches": 4}},
        )
        _, loss = pmodel(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
        np.testing.assert_allclose(
            float(loss.numpy()), float(ref_loss.numpy()),
            rtol=2e-5, atol=2e-6,
        )

    def test_pp_tp_dp_zero_full_hybrid(self):
        """The north-star composition: DP x TP x PP x ZeRO in one call."""
        cfg = _cfg(num_hidden_layers=2, num_attention_heads=2)
        ids = _data(cfg, batch=8)
        paddle.seed(0)
        ref_model = LlamaForCausalLM(cfg)
        _, ref_loss = ref_model(
            paddle.to_tensor(ids), labels=paddle.to_tensor(ids)
        )

        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(
            learning_rate=1e-2, parameters=model.parameters()
        )
        pmodel, opt = dist.parallelize(
            model, opt,
            config={
                "dp_degree": 2, "mp_degree": 2, "pp_degree": 2,
                "dp_config": {"sharding_level": 1},
                "pp_config": {"micro_batches": 4},
            },
        )
        _, loss = pmodel(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
        np.testing.assert_allclose(
            float(loss.numpy()), float(ref_loss.numpy()),
            rtol=2e-5, atol=2e-6,
        )
        # a full eager train step through the rebound optimizer
        loss.backward()
        opt.step()
        opt.clear_grad()
        _, loss2 = pmodel(
            paddle.to_tensor(ids), labels=paddle.to_tensor(ids)
        )
        assert float(loss2.numpy()) < float(loss.numpy())

    def test_pp_tp_grads_match_single_device(self):
        """TP-inside-pipeline gradients vs plain autograd on the same
        weights (the varying-type transposition contract)."""
        cfg = _cfg(num_hidden_layers=2, num_attention_heads=2)
        ids = _data(cfg, batch=4)
        paddle.seed(0)
        ref_model = LlamaForCausalLM(cfg)
        _, ref_loss = ref_model(
            paddle.to_tensor(ids), labels=paddle.to_tensor(ids)
        )
        ref_loss.backward()
        ref_q = ref_model.llama.layers[0].self_attn.q_proj.weight
        ref_emb = ref_model.llama.embed_tokens.weight

        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        pmodel, _ = dist.parallelize(
            model, None,
            config={"mp_degree": 2, "pp_degree": 2,
                    "pp_config": {"micro_batches": 2}},
        )
        _, loss = pmodel(paddle.to_tensor(ids), labels=paddle.to_tensor(ids))
        loss.backward()
        pipe = pmodel._pipe
        # stacked wq grad [n_stages, lps, h, out] -> layer 0 slice
        gq = np.asarray(pipe.stages["wq"].grad.numpy())[0, 0]
        np.testing.assert_allclose(
            gq, ref_q.grad.numpy(), rtol=1e-4, atol=1e-5
        )
        gemb = np.asarray(pipe.first["embed"].grad.numpy())
        np.testing.assert_allclose(
            gemb, ref_emb.grad.numpy(), rtol=1e-4, atol=1e-5
        )
