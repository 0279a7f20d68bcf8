"""Optimizer suite tests.

Mirrors the reference's optimizer tests (test/legacy_test/test_sgd_op.py,
test_adam_op.py, test_adamw_op.py, test_momentum_op.py, ...) at the
integration level: single-step numerics vs a numpy reference, convergence on
a regression problem, state_dict round-trips, grad clip, LR schedulers.
"""
import math

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer as opt
from paddle_tpu.nn.clip import (
    ClipGradByGlobalNorm,
    ClipGradByNorm,
    ClipGradByValue,
)
from paddle_tpu.nn.layer.common import Linear
from paddle_tpu.nn.parameter import Parameter


def _make_param(value):
    p = Parameter(np.asarray(value, dtype=np.float32))
    p.name = "p0"
    return p


def _set_grad(p, g):
    p.grad = paddle.to_tensor(np.asarray(g, dtype=np.float32))


class TestSingleStepNumerics:
    def test_sgd(self):
        p = _make_param([1.0, 2.0])
        _set_grad(p, [0.5, -0.5])
        o = opt.SGD(learning_rate=0.1, parameters=[p])
        o.step()
        np.testing.assert_allclose(p.numpy(), [0.95, 2.05], rtol=1e-6)

    def test_momentum(self):
        p = _make_param([1.0])
        o = opt.Momentum(learning_rate=0.1, momentum=0.9, parameters=[p])
        _set_grad(p, [1.0])
        o.step()  # v=1, p=1-0.1
        np.testing.assert_allclose(p.numpy(), [0.9], rtol=1e-6)
        _set_grad(p, [1.0])
        o.step()  # v=1.9, p=0.9-0.19
        np.testing.assert_allclose(p.numpy(), [0.71], rtol=1e-6)

    def test_momentum_nesterov(self):
        p = _make_param([1.0])
        o = opt.Momentum(
            learning_rate=0.1, momentum=0.9, use_nesterov=True, parameters=[p]
        )
        _set_grad(p, [1.0])
        o.step()  # v=1, p=1-0.1*(1+0.9)
        np.testing.assert_allclose(p.numpy(), [0.81], rtol=1e-6)

    def test_adam_first_step(self):
        p = _make_param([1.0])
        o = opt.Adam(learning_rate=0.1, parameters=[p])
        _set_grad(p, [2.0])
        o.step()
        # t=1: m=0.1*2=0.2, v=0.001*4=0.004
        # lr_t = 0.1*sqrt(1-0.999)/(1-0.9); update = lr_t*m/(sqrt(v)+eps)
        lr_t = 0.1 * math.sqrt(1 - 0.999) / (1 - 0.9)
        expect = 1.0 - lr_t * 0.2 / (math.sqrt(0.004) + 1e-8)
        np.testing.assert_allclose(p.numpy(), [expect], rtol=1e-5)

    def test_adagrad(self):
        p = _make_param([1.0])
        o = opt.Adagrad(learning_rate=0.1, parameters=[p])
        _set_grad(p, [2.0])
        o.step()
        expect = 1.0 - 0.1 * 2.0 / (2.0 + 1e-6)
        np.testing.assert_allclose(p.numpy(), [expect], rtol=1e-5)

    def test_adamw_decoupled_decay(self):
        p = _make_param([1.0])
        o = opt.AdamW(learning_rate=0.1, weight_decay=0.1, parameters=[p])
        _set_grad(p, [0.0])
        o.step()
        # zero grad -> pure decay: p *= (1 - lr*coeff)
        np.testing.assert_allclose(p.numpy(), [0.99], rtol=1e-5)

    def test_adamw_apply_decay_param_fun(self):
        p = _make_param([1.0])
        o = opt.AdamW(
            learning_rate=0.1,
            weight_decay=0.1,
            parameters=[p],
            apply_decay_param_fun=lambda n: False,
        )
        _set_grad(p, [0.0])
        o.step()
        np.testing.assert_allclose(p.numpy(), [1.0], rtol=1e-6)

    def test_rmsprop(self):
        p = _make_param([1.0])
        o = opt.RMSProp(learning_rate=0.1, rho=0.9, epsilon=1e-6,
                        parameters=[p])
        _set_grad(p, [1.0])
        o.step()
        ms = 0.1
        expect = 1.0 - 0.1 * 1.0 / math.sqrt(ms + 1e-6)
        np.testing.assert_allclose(p.numpy(), [expect], rtol=1e-5)

    def test_l2_coupled_regularizer(self):
        p = _make_param([1.0])
        o = opt.SGD(learning_rate=0.1, parameters=[p],
                    weight_decay=paddle.regularizer.L2Decay(0.5))
        _set_grad(p, [0.0])
        o.step()
        # g_eff = 0 + 0.5*1 -> p = 1 - 0.05
        np.testing.assert_allclose(p.numpy(), [0.95], rtol=1e-6)


class TestConvergence:
    def _train(self, optimizer_ctor, steps=200, return_first=False, **kw):
        paddle.seed(0)
        layer = Linear(4, 1)
        rng = np.random.RandomState(0)
        x_np = rng.randn(64, 4).astype(np.float32)
        w_true = np.array([[1.0], [-2.0], [3.0], [0.5]], np.float32)
        y_np = x_np @ w_true + 0.7
        x = paddle.to_tensor(x_np)
        y = paddle.to_tensor(y_np)
        o = optimizer_ctor(parameters=layer.parameters(), **kw)
        loss_val = first = None
        for i in range(steps):
            pred = layer(x)
            loss = ((pred - y) * (pred - y)).mean()
            loss.backward()
            o.step()
            o.clear_grad()
            loss_val = float(loss.numpy())
            if i == 0:
                first = loss_val
        return (loss_val, first) if return_first else loss_val

    def test_sgd_converges(self):
        assert self._train(opt.SGD, learning_rate=0.1) < 1e-3

    def test_momentum_converges(self):
        assert self._train(opt.Momentum, learning_rate=0.05) < 1e-3

    def test_adam_converges(self):
        assert self._train(opt.Adam, learning_rate=0.1) < 1e-3

    def test_adamw_converges(self):
        assert self._train(opt.AdamW, learning_rate=0.1) < 1e-2

    def test_lamb_converges(self):
        assert self._train(opt.Lamb, learning_rate=0.03, steps=300) < 1e-1

    def test_radam_converges(self):
        assert self._train(opt.RAdam, learning_rate=0.1) < 1e-2

    def test_nadam_converges(self):
        assert self._train(opt.NAdam, learning_rate=0.1) < 1e-2

    def test_adadelta_converges(self):
        # Adadelta warms its step-size estimate up from zero; assert a
        # strong relative improvement rather than an absolute floor.
        final, first = self._train(
            opt.Adadelta, learning_rate=1.0, steps=400, return_first=True
        )
        assert final < 0.3 * first

    def test_with_global_norm_clip(self):
        loss = self._train(
            opt.Adam, learning_rate=0.1,
            grad_clip=ClipGradByGlobalNorm(1.0),
        )
        assert loss < 1e-2


class TestGradClip:
    def test_clip_by_value(self):
        clip = ClipGradByValue(max=0.5)
        p = _make_param([1.0, 1.0])
        g = paddle.to_tensor(np.array([2.0, -2.0], np.float32))
        out = clip([(p, g)])
        np.testing.assert_allclose(out[0][1].numpy(), [0.5, -0.5])

    def test_clip_by_norm(self):
        clip = ClipGradByNorm(clip_norm=1.0)
        p = _make_param([1.0, 1.0])
        g = paddle.to_tensor(np.array([3.0, 4.0], np.float32))
        out = clip([(p, g)])
        np.testing.assert_allclose(
            out[0][1].numpy(), [0.6, 0.8], rtol=1e-5
        )

    def test_clip_by_global_norm(self):
        clip = ClipGradByGlobalNorm(clip_norm=1.0)
        p1 = _make_param([1.0])
        p2 = _make_param([1.0])
        g1 = paddle.to_tensor(np.array([3.0], np.float32))
        g2 = paddle.to_tensor(np.array([4.0], np.float32))
        out = clip([(p1, g1), (p2, g2)])
        total = math.sqrt(
            float(out[0][1].numpy() ** 2 + out[1][1].numpy() ** 2)
        )
        np.testing.assert_allclose(total, 1.0, rtol=1e-5)

    def test_global_norm_below_threshold_unchanged(self):
        clip = ClipGradByGlobalNorm(clip_norm=10.0)
        p = _make_param([1.0])
        g = paddle.to_tensor(np.array([3.0], np.float32))
        out = clip([(p, g)])
        np.testing.assert_allclose(out[0][1].numpy(), [3.0], rtol=1e-6)

    def test_need_clip_false_respected(self):
        clip = ClipGradByValue(max=0.5)
        p = _make_param([1.0])
        p.need_clip = False
        _set_grad(p, [2.0])
        o = opt.SGD(learning_rate=1.0, parameters=[p], grad_clip=clip)
        o.step()
        np.testing.assert_allclose(p.numpy(), [-1.0], rtol=1e-6)


class TestStateDict:
    def test_adam_state_roundtrip(self):
        p = _make_param([1.0, 2.0])
        o = opt.Adam(learning_rate=0.1, parameters=[p])
        for _ in range(3):
            _set_grad(p, [0.1, -0.2])
            o.step()
        sd = o.state_dict()
        assert any("moment1" in k for k in sd)
        assert sd["global_step"] == 3

        p2 = _make_param([1.0, 2.0])
        o2 = opt.Adam(learning_rate=0.1, parameters=[p2])
        o2.set_state_dict(sd)
        assert o2._global_step == 3
        st = o2._accumulators[id(p2)]
        st_orig = o._accumulators[id(p)]
        np.testing.assert_allclose(
            np.asarray(st["moment1"]), np.asarray(st_orig["moment1"])
        )

    def test_state_roundtrip_through_save_load(self, tmp_path):
        p = _make_param([1.0, 2.0])
        o = opt.Adam(learning_rate=0.1, parameters=[p])
        _set_grad(p, [0.1, -0.2])
        o.step()
        path = str(tmp_path / "opt.pdopt")
        paddle.save(o.state_dict(), path)
        loaded = paddle.load(path)
        p2 = _make_param([1.0, 2.0])
        o2 = opt.Adam(learning_rate=0.1, parameters=[p2])
        o2.set_state_dict(loaded)
        st = o2._accumulators[id(p2)]
        st_orig = o._accumulators[id(p)]
        np.testing.assert_allclose(
            np.asarray(st["moment2"]), np.asarray(st_orig["moment2"]),
            rtol=1e-6,
        )

    def test_lr_scheduler_state_in_state_dict(self):
        p = _make_param([1.0])
        sched = opt.lr.StepDecay(learning_rate=0.1, step_size=2, gamma=0.5)
        o = opt.Adam(learning_rate=sched, parameters=[p])
        sched.step()
        sd = o.state_dict()
        assert "LR_Scheduler" in sd
        assert sd["LR_Scheduler"]["last_epoch"] == 1


class TestLRSchedulers:
    def test_step_decay(self):
        s = opt.lr.StepDecay(learning_rate=1.0, step_size=2, gamma=0.1)
        vals = [s()]
        for _ in range(4):
            s.step()
            vals.append(s())
        np.testing.assert_allclose(vals, [1.0, 1.0, 0.1, 0.1, 0.01], rtol=1e-6)

    def test_multistep_decay(self):
        s = opt.lr.MultiStepDecay(1.0, milestones=[2, 4], gamma=0.5)
        vals = []
        for _ in range(5):
            vals.append(s())
            s.step()
        np.testing.assert_allclose(vals, [1.0, 1.0, 0.5, 0.5, 0.25], rtol=1e-6)

    def test_exponential_decay(self):
        s = opt.lr.ExponentialDecay(2.0, gamma=0.5)
        s.step()
        assert abs(s() - 1.0) < 1e-9

    def test_cosine_annealing(self):
        s = opt.lr.CosineAnnealingDecay(1.0, T_max=10)
        assert abs(s() - 1.0) < 1e-9
        s.step(10)
        assert abs(s() - 0.0) < 1e-9
        s.step(5)
        assert abs(s() - 0.5) < 1e-9

    def test_linear_warmup(self):
        s = opt.lr.LinearWarmup(
            learning_rate=0.5, warmup_steps=5, start_lr=0.0, end_lr=0.5
        )
        assert abs(s() - 0.0) < 1e-9
        s.step()
        assert abs(s() - 0.1) < 1e-9
        for _ in range(5):
            s.step()
        assert abs(s() - 0.5) < 1e-9

    def test_polynomial_decay(self):
        s = opt.lr.PolynomialDecay(1.0, decay_steps=10, end_lr=0.0, power=1.0)
        s.step(5)
        assert abs(s() - 0.5) < 1e-9

    def test_piecewise(self):
        s = opt.lr.PiecewiseDecay(boundaries=[3, 6], values=[1.0, 0.5, 0.1])
        s.step(0)
        assert s() == 1.0
        s.step(4)
        assert s() == 0.5
        s.step(7)
        assert s() == 0.1

    def test_noam(self):
        s = opt.lr.NoamDecay(d_model=512, warmup_steps=10, learning_rate=1.0)
        s.step(5)
        expect = (512 ** -0.5) * 5 * (10 ** -1.5)
        assert abs(s() - expect) < 1e-9

    def test_reduce_on_plateau(self):
        s = opt.lr.ReduceOnPlateau(1.0, patience=1, factor=0.5)
        s.step(1.0)
        s.step(1.0)
        s.step(1.0)
        assert abs(s() - 0.5) < 1e-9

    def test_lambda_decay(self):
        s = opt.lr.LambdaDecay(1.0, lr_lambda=lambda e: 1.0 / (e + 1))
        s.step(3)
        assert abs(s() - 0.25) < 1e-9

    def test_one_cycle(self):
        s = opt.lr.OneCycleLR(max_learning_rate=1.0, total_steps=100)
        start = s()
        for _ in range(29):
            s.step()
        near_peak = s()
        assert near_peak > start

    def test_scheduler_drives_optimizer(self):
        p = _make_param([1.0])
        sched = opt.lr.StepDecay(learning_rate=1.0, step_size=1, gamma=0.1)
        o = opt.SGD(learning_rate=sched, parameters=[p])
        _set_grad(p, [1.0])
        o.step()  # lr=1.0
        np.testing.assert_allclose(p.numpy(), [0.0], atol=1e-6)
        sched.step()  # lr -> 0.1
        _set_grad(p, [1.0])
        o.step()
        np.testing.assert_allclose(p.numpy(), [-0.1], atol=1e-6)

    def test_scheduler_state_dict_roundtrip(self):
        s = opt.lr.CosineAnnealingDecay(1.0, T_max=10)
        s.step()
        s.step()
        sd = s.state_dict()
        s2 = opt.lr.CosineAnnealingDecay(1.0, T_max=10)
        s2.set_state_dict(sd)
        assert s2.last_epoch == s.last_epoch
        assert abs(s2() - s()) < 1e-12


class TestParamGroups:
    def test_per_group_lr(self):
        p1 = _make_param([1.0])
        p2 = Parameter(np.asarray([1.0], np.float32))
        p2.name = "p1"
        o = opt.SGD(
            learning_rate=0.1,
            parameters=[
                {"params": [p1]},
                {"params": [p2], "learning_rate": 10.0},
            ],
        )
        _set_grad(p1, [1.0])
        _set_grad(p2, [1.0])
        o.step()
        np.testing.assert_allclose(p1.numpy(), [0.9], rtol=1e-6)
        np.testing.assert_allclose(p2.numpy(), [0.0], atol=1e-6)

    def test_param_without_grad_skipped(self):
        p1 = _make_param([1.0])
        p2 = Parameter(np.asarray([5.0], np.float32))
        o = opt.SGD(learning_rate=0.1, parameters=[p1, p2])
        _set_grad(p1, [1.0])
        o.step()
        np.testing.assert_allclose(p2.numpy(), [5.0])

    def test_multi_precision_master_weights(self):
        p = Parameter(np.asarray([1.0, 2.0], np.float32))
        p._rebind(p._data.astype("bfloat16"))
        p.name = "bf"
        o = opt.Adam(learning_rate=0.001, parameters=[p],
                     multi_precision=True)
        for _ in range(5):
            p.grad = paddle.to_tensor(
                np.asarray([0.01, 0.01], np.float32)
            )
            o.step()
        st = o._accumulators[id(p)]
        assert "master_weight" in st
        assert str(st["master_weight"].dtype) == "float32"
        assert p.dtype.name == "bfloat16"


class TestMisc:
    def test_minimize(self):
        layer = Linear(2, 1)
        x = paddle.to_tensor(np.ones((4, 2), np.float32))
        o = opt.SGD(learning_rate=0.1, parameters=layer.parameters())
        loss = layer(x).mean()
        o.minimize(loss)
        assert all(p.grad is not None for p in layer.parameters())

    def test_clear_grad(self):
        p = _make_param([1.0])
        _set_grad(p, [1.0])
        o = opt.SGD(learning_rate=0.1, parameters=[p])
        o.clear_grad()
        assert p.grad is None

    def test_set_lr(self):
        p = _make_param([1.0])
        o = opt.SGD(learning_rate=0.1, parameters=[p])
        o.set_lr(0.5)
        assert o.get_lr() == 0.5

    def test_set_lr_rejected_with_scheduler(self):
        p = _make_param([1.0])
        o = opt.SGD(
            learning_rate=opt.lr.StepDecay(0.1, step_size=1), parameters=[p]
        )
        with pytest.raises(RuntimeError):
            o.set_lr(0.5)

    def test_parameters_required(self):
        with pytest.raises(ValueError):
            opt.SGD(learning_rate=0.1)


class TestReviewRegressions:
    def test_adamw_applies_param_regularizer(self):
        # per-param coupled regularizer must apply under AdamW too
        p = _make_param([1.0])
        p.regularizer = paddle.regularizer.L2Decay(0.5)
        o = opt.AdamW(learning_rate=0.1, weight_decay=0.0, parameters=[p])
        _set_grad(p, [0.0])
        o.step()
        assert float(p.numpy()[0]) < 1.0  # decayed via coupled reg

    def test_split_tensor_sections(self):
        x = paddle.to_tensor(np.arange(12).reshape(3, 4).astype(np.float32))
        parts = paddle.split(x, paddle.to_tensor(np.array([1, 3], np.int32)),
                             axis=-1)
        assert [list(p.shape) for p in parts] == [[3, 1], [3, 3]]
        parts = paddle.split(x, [paddle.to_tensor(np.int32(1)), 2, -1],
                             axis=-1)
        assert [list(p.shape) for p in parts] == [[3, 1], [3, 2], [3, 1]]

    def test_bf16_params_and_moments_keep_their_dtype(self):
        """lr and t enter the update as float32: without master weights a
        bf16 parameter used to come back float32 after one step, its
        moments after two, and a staged TrainStep retraced while the
        dtypes settled (the chip's "slow first steps")."""
        from paddle_tpu.observability import jit_events

        paddle.seed(0)
        layer = Linear(8, 8)
        layer.bfloat16()
        o = opt.AdamW(learning_rate=1e-2, parameters=layer.parameters())
        step = paddle.jit.TrainStep(
            layer, lambda m, x: (m(x) ** 2).mean(), o)
        x = paddle.to_tensor(np.ones((4, 8), np.float32)).astype("bfloat16")
        jit_events.clear_compile_log()
        for _ in range(3):
            step(x)
        assert len([e for e in jit_events.compile_log()
                    if e["kind"] == "train_step"]) == 1
        for p in layer.parameters():
            assert p.dtype == paddle.bfloat16
            assert {str(v.dtype) for v in o._accumulators[id(p)].values()
                    } == {"bfloat16"}

    def test_multiplicative_decay_incremental(self):
        s = opt.lr.MultiplicativeDecay(1.0, lr_lambda=lambda e: 0.5)
        for _ in range(3):
            s.step()
        assert abs(s() - 0.125) < 1e-12


class TestChunkedStep:
    def test_chunked_matches_fused(self):
        """step_chunk=1 (per-leaf update programs) must produce exactly
        the fused whole-tree update."""
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        def build():
            paddle.seed(0)
            m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4))
            o = paddle.optimizer.AdamW(
                learning_rate=1e-2, parameters=m.parameters())
            return m, o

        x = paddle.to_tensor(
            np.random.RandomState(0).randn(4, 8).astype("float32"))

        def train(m, o, steps=3):
            for _ in range(steps):
                loss = (m(x) ** 2).mean()
                loss.backward()
                o.step()
                o.clear_grad()
            return [p.numpy() for p in m.parameters()]

        m1, o1 = build()
        ref = train(m1, o1)
        m2, o2 = build()
        o2.step_chunk = 1
        got = train(m2, o2)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)

    def test_chunked_with_global_clip_matches_fused(self):
        """Global-norm clipping must see the whole gradient tree even
        under chunked stepping (clip-once-then-chunk)."""
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        def build():
            paddle.seed(1)
            m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                              nn.Linear(16, 4))
            o = paddle.optimizer.AdamW(
                learning_rate=1e-1, parameters=m.parameters(),
                grad_clip=nn.ClipGradByGlobalNorm(0.01),
            )
            return m, o

        x = paddle.to_tensor(
            np.random.RandomState(1).randn(4, 8).astype("float32") * 10)

        def train(m, o):
            for _ in range(2):
                loss = (m(x) ** 2).mean()
                loss.backward()
                o.step()
                o.clear_grad()
            return [p.numpy() for p in m.parameters()]

        m1, o1 = build()
        ref = train(m1, o1)
        m2, o2 = build()
        o2.step_chunk = 1
        got = train(m2, o2)
        for a, b in zip(got, ref):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)

    def test_bad_step_chunk_raises(self):
        import numpy as np

        import paddle_tpu as paddle
        import paddle_tpu.nn as nn
        import pytest

        m = nn.Linear(4, 4)
        o = paddle.optimizer.SGD(learning_rate=0.1,
                                 parameters=m.parameters())
        o.step_chunk = -1
        x = paddle.to_tensor(np.ones((2, 4), "float32"))
        m(x).sum().backward()
        with pytest.raises(ValueError, match="positive"):
            o.step()
