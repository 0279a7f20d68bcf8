"""Pallas kernel tests (interpret mode on CPU; real kernels on TPU).

ref test strategy: numeric comparison of the fused kernel against the
math fallback (the reference tests flash_attention against the unfused
computation, test/legacy_test/test_flash_attention.py).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.kernels.pallas.flash_attention import flash_attention


def _ref(q, k, v, causal, scale=None):
    d = q.shape[-1]
    s = np.einsum(
        "bqhd,bkhd->bhqk", q.astype(np.float64), k.astype(np.float64)
    ) * (scale or 1.0 / np.sqrt(d))
    if causal:
        m = np.tril(np.ones(s.shape[-2:], bool))
        s = np.where(m, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v.astype(np.float64)).astype(
        np.float32
    )


@pytest.fixture
def qkv():
    rng = np.random.RandomState(0)
    mk = lambda: rng.randn(2, 256, 2, 64).astype(np.float32)
    return mk(), mk(), mk()


class TestFlashAttention:
    def test_full_matches_math(self, qkv):
        q, k, v = qkv
        out = np.asarray(
            flash_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=False
            )
        )
        np.testing.assert_allclose(
            out, _ref(q, k, v, False), rtol=2e-4, atol=2e-5
        )

    def test_causal_matches_math(self, qkv):
        q, k, v = qkv
        out = np.asarray(
            flash_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True
            )
        )
        np.testing.assert_allclose(
            out, _ref(q, k, v, True), rtol=2e-4, atol=2e-5
        )

    def test_cross_attention_lengths(self):
        rng = np.random.RandomState(1)
        q = rng.randn(1, 128, 2, 64).astype(np.float32)
        k = rng.randn(1, 384, 2, 64).astype(np.float32)
        v = rng.randn(1, 384, 2, 64).astype(np.float32)
        out = np.asarray(
            flash_attention(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=False
            )
        )
        np.testing.assert_allclose(
            out, _ref(q, k, v, False), rtol=2e-4, atol=2e-5
        )

    def test_gradients_match_math(self, qkv):
        q, k, v = qkv

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()

        def loss_math(q, k, v):
            qf, kf, vf = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(q.shape[-1])
            mask = jnp.tril(jnp.ones(s.shape[-2:], bool))
            s = jnp.where(mask, s, -1e30)
            p = jax.nn.softmax(s, -1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, vf).sum()

        args = tuple(jnp.asarray(x) for x in (q, k, v))
        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(*args)
        g2 = jax.grad(loss_math, argnums=(0, 1, 2))(*args)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5
            )

    def test_sdpa_dispatches_to_pallas(self):
        """The op routes causal/no-mask calls through the kernel when the
        flag is set (min-seq lowered for the test), and both paths agree."""
        rng = np.random.RandomState(2)
        q = paddle.to_tensor(rng.randn(1, 128, 2, 64).astype(np.float32))
        try:
            paddle.set_flags({"FLAGS_flash_attention_min_seq": 128})
            with_flag = paddle.scaled_dot_product_attention(
                q, q, q, None, 0.0, True
            ).numpy()
            paddle.set_flags({"FLAGS_use_pallas_kernels": False})
            math_out = paddle.scaled_dot_product_attention(
                q, q, q, None, 0.0, True
            ).numpy()
        finally:
            paddle.set_flags({"FLAGS_use_pallas_kernels": True,
                              "FLAGS_flash_attention_min_seq": 2048})
        np.testing.assert_allclose(with_flag, math_out, rtol=2e-4, atol=2e-5)

    def test_sdpa_fallback_on_mask(self):
        """Masked/dropout calls stay on the math path (kernel contract)."""
        rng = np.random.RandomState(3)
        q = paddle.to_tensor(rng.randn(1, 128, 2, 64).astype(np.float32))
        mask = paddle.to_tensor(
            np.zeros((1, 1, 128, 128), np.float32)
        )
        out = paddle.scaled_dot_product_attention(q, q, q, mask)
        assert out.shape == [1, 128, 2, 64]

    def test_bf16_path(self, qkv):
        q, k, v = (x.astype(jnp.bfloat16) for x in map(jnp.asarray, qkv))
        out = flash_attention(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        ref = _ref(*[np.asarray(x, np.float32) for x in (q, k, v)], True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, rtol=2e-2, atol=2e-2
        )

    def test_llama_uses_flash_when_eligible(self):
        """End to end: Llama attention at seq=128 hits the kernel path
        (min-seq lowered) and still trains."""
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        paddle.set_flags({"FLAGS_flash_attention_min_seq": 128})
        try:
            m = LlamaForCausalLM(LlamaConfig.tiny(hidden_size=128,
                                                  num_attention_heads=2))
            ids = paddle.to_tensor(
                np.random.randint(0, 128, (2, 128)).astype(np.int32)
            )
            logits, loss = m(ids, labels=ids)
            loss.backward()
            assert all(p.grad is not None for p in m.parameters())
        finally:
            paddle.set_flags({"FLAGS_flash_attention_min_seq": 2048})


# ----------------------------------------------------------------------
# flash attention's tiling: the block chooser, unequal blocks, the causal
# schedule (clamped index maps, unmasked interior blocks), the series
# that says which tile a call got
# ----------------------------------------------------------------------
from paddle_tpu.kernels.pallas import _compat  # noqa: E402
from paddle_tpu.kernels.pallas import flash_attention as fa  # noqa: E402


def _math_sdpa(q, k, v, causal):
    """The unfused computation in float32 on the inputs as given (the
    kernel masks with top-left aligned positions)."""
    qf, kf, vf = (jnp.swapaxes(x, 1, 2).astype(jnp.float32)
                  for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) / np.sqrt(q.shape[-1])
    if causal:
        ql, kl = s.shape[-2:]
        s = jnp.where(
            jnp.arange(ql)[:, None] >= jnp.arange(kl)[None, :], s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vf), 1, 2)


def _out_and_grads(fn, q, k, v, w):
    """Output and the three gradients of sum(fn(q, k, v) * w), float32."""
    out, vjp = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), q, k, v)
    return [np.asarray(x, np.float32) for x in (out, *vjp(w))]


def _inputs(seed, sq, sk, dtype, heads=2, d=64):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.randn(1, s, heads, d), dtype)
    return mk(sq), mk(sk), mk(sk), jnp.asarray(
        rng.randn(1, sq, heads, d), jnp.float32)


def _worst(got, want):
    """Largest |got - want| over the largest |want|, per tensor."""
    return max(float(np.abs(g - w).max() / np.abs(w).max())
               for g, w in zip(got, want))


# float32 inputs: reduction order alone differs from the math form.
# bf16 inputs: q (with the scale folded in), p and ds enter the MXU
# rounded to bf16 (2**-9 = 2e-3 relative each) and the results are
# stored in bf16; against the float32 math on the same bf16 inputs the
# worst element of an output or gradient lies within 2e-2 of its
# tensor's largest (8e-3 is the most these cases read).
_TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashTiling:
    @pytest.mark.parametrize("kernel", fa.KERNELS)
    @pytest.mark.parametrize("seq,want", [(2176, 128), (4864, 256)])
    def test_chooser_takes_a_divisor(self, kernel, seq, want):
        assert fa.choose_blocks(seq, seq, 128, jnp.bfloat16, kernel) == (
            want, want)

    @pytest.mark.parametrize("kernel", fa.KERNELS)
    def test_chooser_gives_4096_the_large_tile(self, kernel):
        bq, bk = fa.choose_blocks(4096, 4096, 128, jnp.bfloat16, kernel)
        assert 4096 % bq == 0 and 4096 % bk == 0
        assert bq * bk >= 512 * 512
        assert fa._vmem_bytes(kernel, bq, bk, 128, 2) <= fa.VMEM_BUDGET_BYTES

    @pytest.mark.parametrize("d", [64, 128, 256])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_chooser_fits_the_budget_at_every_width(self, d, dtype):
        size = jnp.dtype(dtype).itemsize
        for kernel in fa.KERNELS:
            bq, bk = fa.choose_blocks(4096, 4096, d, dtype, kernel)
            assert bq % 128 == 0 and bk % 128 == 0
            assert fa._vmem_bytes(kernel, bq, bk, d, size) \
                <= fa.VMEM_BUDGET_BYTES
            # nothing that fits has fewer grid steps
            assert all(
                fa._vmem_bytes(kernel, cq, ck, d, size)
                > fa.VMEM_BUDGET_BYTES
                for cq in (512, 1024) for ck in (512, 1024)
                if cq * ck > bq * bk)

    @pytest.mark.parametrize("d", [128, 256])
    def test_float32_and_the_backward_get_no_larger_tile(self, d):
        area = lambda dtype, kernel: int(np.prod(
            fa.choose_blocks(4096, 4096, d, dtype, kernel)))
        for kernel in fa.KERNELS:
            assert area(jnp.float32, kernel) <= area(jnp.bfloat16, kernel)
        for dtype in (jnp.float32, jnp.bfloat16):
            assert area(dtype, fa.BWD_DQ) <= area(dtype, fa.FWD)
            assert area(dtype, fa.BWD_DKV) <= area(dtype, fa.FWD)
        # the backward holds more [bq, bk] tiles than the forward
        assert fa._vmem_bytes(fa.BWD_DKV, 512, 512, d, 2) \
            > fa._vmem_bytes(fa.BWD_DQ, 512, 512, d, 2) \
            > fa._vmem_bytes(fa.FWD, 512, 512, d, 2)

    def test_chooser_breaks_a_tie_as_the_chip_measured(self, monkeypatch):
        """Where the square does not fit, the forward keeps the wide
        block_k and the backward the tall block_q."""
        monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 20 * 2**20)
        pick = lambda kernel: fa.choose_blocks(
            4096, 4096, 128, jnp.bfloat16, kernel)
        assert pick(fa.FWD) == (512, 1024)
        assert pick(fa.BWD_DQ) == pick(fa.BWD_DKV) == (1024, 512)

    def test_chooser_short_and_unequal_lengths(self):
        # a length under 128 is one block; each side gets its own divisor
        assert fa.choose_blocks(64, 64, 64, jnp.float32, fa.FWD) == (64, 64)
        bq, bk = fa.choose_blocks(1024, 2176, 128, jnp.bfloat16, fa.FWD)
        assert (bq, bk) == (1024, 128)

    def test_indivisible_length_is_refused(self):
        q = jnp.zeros((1, 200, 1, 64), jnp.float32)
        with pytest.raises(ValueError, match="divisible by the block"):
            flash_attention(q, q, q)
        q = jnp.zeros((1, 512, 1, 64), jnp.float32)
        with pytest.raises(ValueError, match="block_q=384"):
            flash_attention(q, q, q, block_q=384)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
    def test_unequal_blocks_match_math(self, block_q, block_k, causal,
                                       dtype):
        """Four blocks of the larger side: causal calls meet skipped,
        diagonal and interior blocks."""
        q, k, v, w = _inputs(10, 1024, 1024, dtype)
        got = _out_and_grads(
            lambda *a: flash_attention(
                *a, causal=causal, block_q=block_q, block_k=block_k),
            q, k, v, w)
        want = _out_and_grads(
            lambda *a: _math_sdpa(*a, causal), q, k, v, w)
        assert _worst(got, want) < _TOL[dtype]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128)])
    def test_unequal_lengths_unequal_blocks(self, block_q, block_k, dtype):
        q, k, v, w = _inputs(11, 512, 1024, dtype)
        got = _out_and_grads(
            lambda *a: flash_attention(
                *a, causal=False, block_q=block_q, block_k=block_k),
            q, k, v, w)
        want = _out_and_grads(
            lambda *a: _math_sdpa(*a, False), q, k, v, w)
        assert _worst(got, want) < _TOL[dtype]

    @pytest.mark.parametrize("block_q,block_k",
                             [(256, 256), (512, 128), (128, 512)])
    def test_causal_schedule_agrees_with_128_tiles(self, block_q, block_k):
        """Whole blocks above the diagonal (their index maps clamped to
        the resident block) and unmasked blocks below it: same answer as
        the 128 x 128 sweep and as the math form."""
        q, k, v, w = _inputs(12, 1024, 1024, jnp.float32)
        run = lambda bq, bk: _out_and_grads(
            lambda *a: flash_attention(
                *a, causal=True, block_q=bq, block_k=bk), q, k, v, w)
        got = run(block_q, block_k)
        assert _worst(got, run(128, 128)) < 2e-5
        assert _worst(got, _out_and_grads(
            lambda *a: _math_sdpa(*a, True), q, k, v, w)) < 2e-5

    def test_causal_with_more_keys_than_queries(self):
        """k blocks no q block sees get zero gradients; the clamped index
        maps stay inside the arrays."""
        q, k, v, w = _inputs(13, 256, 512, jnp.float32)
        got = _out_and_grads(
            lambda *a: flash_attention(
                *a, causal=True, block_q=128, block_k=128), q, k, v, w)
        want = _out_and_grads(
            lambda *a: _math_sdpa(*a, True), q, k, v, w)
        assert _worst(got, want) < 2e-5
        assert not got[2][:, 256:].any() and not got[3][:, 256:].any()

    @pytest.mark.parametrize("seq", [2176, 4096, 4864])
    def test_chosen_tiles_lower_to_mosaic(self, seq):
        """The serving buckets' lengths (17 x 128, 19 x 256) and the
        training cell's trace and lower as Mosaic kernels with the tiles
        the chooser gives them (the lowering needs no TPU; what the TPU's
        compiler then refuses, the described-v5e compile shows)."""
        from unittest import mock

        q = jax.ShapeDtypeStruct((1, seq, 2, 128), jnp.bfloat16)
        grad = jax.grad(lambda *a: flash_attention(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
        with mock.patch.object(_compat, "on_tpu", lambda: True):
            text = jax.jit(grad).trace(q, q, q).lower(
                lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 3

    def test_flash_blocks_series_moves_once_a_traced_call(self):
        q, k, v, w = _inputs(14, 256, 256, jnp.float32)
        chosen = {
            kernel: (kernel, *fa.choose_blocks(256, 256, 64, q.dtype, kernel))
            for kernel in fa.KERNELS}
        moved = lambda before: {
            key: n - before.get(key, 0)
            for key, n in _compat.flash_blocks().items()
            if n != before.get(key, 0)}

        fwd = jax.jit(lambda *a: flash_attention(*a, causal=True))
        before = _compat.flash_blocks()
        fwd(q, k, v)
        fwd(q, k, v)  # compiled: not traced again
        assert moved(before) == {chosen[fa.FWD]: 1}

        before = _compat.flash_blocks()
        jax.jit(jax.grad(
            lambda *a: flash_attention(*a, causal=True).sum()))(q, k, v)
        assert moved(before) == {chosen[kernel]: 1 for kernel in fa.KERNELS}

        # a caller's blocks win, for all three kernels
        before = _compat.flash_blocks()
        jax.grad(lambda *a: flash_attention(
            *a, causal=True, block_q=128, block_k=64).sum())(q, k, v)
        assert moved(before) == {
            (kernel, 128, 64): 1 for kernel in fa.KERNELS}
        from paddle_tpu.observability import get_registry

        assert 'paddle_tpu_kernels_flash_blocks{block_k="64",block_q="128"' \
            in get_registry().render_prometheus()


# ----------------------------------------------------------------------
# grouped_matmul: ragged grouped GEMM (interpret-mode kernel vs the
# ragged_dot fallback vs an explicit numpy oracle)
# ----------------------------------------------------------------------
from paddle_tpu.kernels.pallas.grouped_matmul import (  # noqa: E402
    grouped_matmul,
)


def _gmm_ref(lhs, rhs, group_sizes, scales=None):
    w = rhs.astype(np.float64)
    if scales is not None:
        w = w * scales.astype(np.float64)[:, None, :]
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float64)
    o = 0
    for g, n in enumerate(group_sizes):
        out[o:o + n] = lhs[o:o + n].astype(np.float64) @ w[g]
        o += n
    return out.astype(np.float32)


class TestGroupedMatmul:
    # ragged segment sweeps: empty experts (leading/trailing/interior),
    # single-token segments, everything-on-one-expert
    SWEEP = [
        [5, 0, 11, 16],
        [0, 0, 32, 0],
        [1, 1, 1, 29],
        [32, 0, 0, 0],
        [0, 7, 1, 24],
    ]

    def _case(self, gs, seed=0, k=24, m=40):
        rng = np.random.RandomState(seed)
        lhs = rng.randn(sum(gs), k).astype(np.float32)
        rhs = rng.randn(len(gs), k, m).astype(np.float32)
        return (jnp.asarray(lhs), jnp.asarray(rhs),
                jnp.asarray(np.array(gs, np.int32)))

    @pytest.mark.parametrize("gs", SWEEP)
    def test_interpret_kernel_matches_ref(self, gs):
        lhs, rhs, gsa = self._case(gs)
        out = np.asarray(grouped_matmul(lhs, rhs, gsa, impl="pallas"))
        np.testing.assert_allclose(
            out, _gmm_ref(np.asarray(lhs), np.asarray(rhs), gs),
            rtol=1e-5, atol=1e-5,
        )

    @pytest.mark.parametrize("gs", SWEEP)
    def test_fallback_matches_kernel(self, gs):
        lhs, rhs, gsa = self._case(gs, seed=1)
        out_p = np.asarray(grouped_matmul(lhs, rhs, gsa, impl="pallas"))
        out_x = np.asarray(grouped_matmul(lhs, rhs, gsa, impl="xla"))
        np.testing.assert_allclose(out_p, out_x, rtol=1e-5, atol=1e-6)

    def test_small_tile_and_row_padding(self):
        # n not a multiple of the tile: rows pad internally, slice back
        lhs, rhs, gsa = self._case([3, 2, 5, 1], k=12, m=10)
        out = np.asarray(grouped_matmul(lhs, rhs, gsa, impl="pallas"))
        np.testing.assert_allclose(
            out, _gmm_ref(np.asarray(lhs), np.asarray(rhs), [3, 2, 5, 1]),
            rtol=1e-5, atol=1e-5,
        )

    def test_gradients_match_fallback(self):
        lhs, rhs, gsa = self._case([5, 0, 11, 16], seed=2)

        def loss(impl):
            return lambda a, b: grouped_matmul(
                a, b, gsa, impl=impl
            ).sum()

        gp = jax.grad(loss("pallas"), argnums=(0, 1))(lhs, rhs)
        gx = jax.grad(loss("xla"), argnums=(0, 1))(lhs, rhs)
        for a, b in zip(gp, gx):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            )

    def test_int8_dequant_in_kernel(self):
        gs = [5, 0, 11, 16]
        lhs, rhs, gsa = self._case(gs, seed=3)
        w = np.asarray(rhs)
        scales = np.maximum(np.abs(w).max(axis=1), 1e-8) / 127.0
        q = np.clip(
            np.round(w / scales[:, None, :]), -127, 127
        ).astype(np.int8)
        out_p = np.asarray(grouped_matmul(
            lhs, jnp.asarray(q), gsa, rhs_scales=jnp.asarray(scales),
            impl="pallas",
        ))
        out_x = np.asarray(grouped_matmul(
            lhs, jnp.asarray(q), gsa, rhs_scales=jnp.asarray(scales),
            impl="xla",
        ))
        # the two int8 paths agree tightly; both sit within the
        # documented quantization tolerance of the fp32 oracle
        np.testing.assert_allclose(out_p, out_x, rtol=1e-4, atol=1e-4)
        ref = _gmm_ref(np.asarray(lhs), w, gs)
        err = np.abs(out_p - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 0.02, err

    def test_jit_with_traced_group_sizes(self):
        gs = [5, 0, 11, 16]
        lhs, rhs, gsa = self._case(gs, seed=4)
        f = jax.jit(lambda a, b, g: grouped_matmul(a, b, g, impl="pallas"))
        np.testing.assert_allclose(
            np.asarray(f(lhs, rhs, gsa)),
            _gmm_ref(np.asarray(lhs), np.asarray(rhs), gs),
            rtol=1e-5, atol=1e-5,
        )

    def test_bad_impl_rejected(self):
        lhs, rhs, gsa = self._case([4, 4, 4, 4])
        with pytest.raises(ValueError, match="impl"):
            grouped_matmul(lhs, rhs, gsa, impl="cuda")


# ----------------------------------------------------------------------
# paged decode attention: interpret-mode kernel vs the XLA fallback,
# fp32 and int8-quantized pools
# ----------------------------------------------------------------------
from paddle_tpu.kernels.pallas.paged_attention import (  # noqa: E402
    paged_attention,
    paged_attention_xla,
    quantize_tokens,
    update_pages,
)


class TestPagedAttention:
    def _pool(self, seed=0, kvh=2, pages=10, bs=8, d=32):
        rng = np.random.RandomState(seed)
        kp = rng.randn(kvh, pages, bs, d).astype(np.float32)
        vp = rng.randn(kvh, pages, bs, d).astype(np.float32)
        return kp, vp

    def test_parity_partial_and_zero_lengths(self):
        # lengths sweep: length-0 slot (exact zeros), a mid-page partial
        # last block, a page-aligned length, and full capacity
        kp, vp = self._pool()
        rng = np.random.RandomState(1)
        q = rng.randn(4, 4, 32).astype(np.float32)       # GQA group=2
        bt = rng.randint(0, 10, (4, 3)).astype(np.int32)
        lens = np.array([0, 5, 16, 24], np.int32)
        args = tuple(map(jnp.asarray, (q, kp, vp, bt, lens)))
        out_p = np.asarray(paged_attention(*args))
        out_x = np.asarray(paged_attention_xla(*args))
        np.testing.assert_allclose(out_p, out_x, rtol=2e-5, atol=2e-5)
        assert np.all(out_p[0] == 0.0) and np.all(out_x[0] == 0.0)

    def test_block_table_reuse_after_free(self):
        # a freed block's stale contents must be invisible to the next
        # tenant: write seq A over pages [2, 3], then remap the same
        # physical pages to seq B with a SHORTER length — positions past
        # B's length hold A's stale rows and must be masked out
        kp, vp = self._pool(seed=2)
        q = np.random.RandomState(3).randn(1, 2, 32).astype(np.float32)
        bt = np.array([[2, 3]], np.int32)
        full = np.array([16], np.int32)
        short = np.array([3], np.int32)
        argf = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(full))
        args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(bt), jnp.asarray(short))
        out_full = np.asarray(paged_attention(*argf))
        out_short = np.asarray(paged_attention(*args))
        assert np.abs(out_full - out_short).max() > 1e-4  # mask matters
        # oracle over only the first `short` rows of the mapped pages
        ctx_k = kp[:, bt[0]].reshape(2, -1, 32)[:, :3]
        ctx_v = vp[:, bt[0]].reshape(2, -1, 32)[:, :3]
        s = np.einsum(
            "hd,hkd->hk", q[0].astype(np.float64),
            ctx_k.astype(np.float64),
        ) / np.sqrt(32)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hk,hkd->hd", p, ctx_v.astype(np.float64))
        np.testing.assert_allclose(
            out_short[0], ref.astype(np.float32), rtol=2e-5, atol=2e-5
        )

    def test_int8_pool_tolerance(self):
        kp, vp = self._pool(seed=4)
        rng = np.random.RandomState(5)
        q = rng.randn(3, 2, 32).astype(np.float32)
        bt = rng.randint(0, 10, (3, 3)).astype(np.int32)
        lens = np.array([7, 20, 24], np.int32)
        kq = quantize_tokens(jnp.asarray(kp))
        vq = quantize_tokens(jnp.asarray(vp))
        out_q = np.asarray(paged_attention(
            jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(lens)
        ))
        out_qx = np.asarray(paged_attention_xla(
            jnp.asarray(q), kq, vq, jnp.asarray(bt), jnp.asarray(lens)
        ))
        out_f = np.asarray(paged_attention_xla(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lens)
        ))
        # kernel and fallback dequantize identically...
        np.testing.assert_allclose(out_q, out_qx, rtol=1e-4, atol=1e-5)
        # ...and both sit within the documented int8 KV tolerance of
        # the float pool (docs/kernels.md)
        np.testing.assert_allclose(out_q, out_f, rtol=0.05, atol=0.05)

    def test_int8_update_pages_roundtrip(self):
        kp, vp = self._pool(seed=6, kvh=2, pages=4, bs=4, d=16)
        kq = quantize_tokens(jnp.asarray(kp))
        vq = quantize_tokens(jnp.asarray(vp))
        rng = np.random.RandomState(7)
        kn = rng.randn(2, 2, 16).astype(np.float32)
        vn = rng.randn(2, 2, 16).astype(np.float32)
        bt = np.array([[0, 1], [2, 3]], np.int32)
        lens = np.array([5, 8], np.int32)  # seq1 at page-capacity slot 0
        (k2, ks2), (v2, vs2) = update_pages(
            kq, vq, jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(bt), jnp.asarray(lens),
        )
        # seq 0's token landed at page bt[0,1]=1 slot 1, within 1%
        deq = np.asarray(k2)[:, 1, 1] * np.asarray(ks2)[:, 1, 1][:, None]
        np.testing.assert_allclose(deq, kn[0], rtol=0.02, atol=0.02)
        # untouched slots keep their prior quantized contents + scales
        assert np.array_equal(
            np.asarray(k2)[:, 3, 2], np.asarray(kq[0])[:, 3, 2]
        )
        assert np.array_equal(
            np.asarray(ks2)[:, 3, 2], np.asarray(kq[1])[:, 3, 2]
        )
