"""The Mellum2-12B-A2.5B configuration's benchmark files on the CPU at a
tiny size: the configuration against the catalog row's widths, the leaf
list against the model's creation order, the parameter count, the runner's
control flow with the program against the plain reference (each step's
loss, every leaf's first gradient, every leaf's change after three AdamW
steps), each control and planted fault coming out not correct (this
model's own, the window and YaRN left out, among them), the arithmetic of
work_mellum2.py against hand-worked values, and the new kernel files'
patterns against instruction texts.

Entries of BENCHMARK.json are found by membership (`CELL in
entry["workloads"]`), never by position or by `== [CELL]`.
"""
import json
import os
import re
import statistics
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce as R                        # noqa: E402
from benchmarks import run as run_mod                     # noqa: E402
from benchmarks import weights_mellum2 as W               # noqa: E402
from benchmarks import work_mellum2 as work               # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "mellum2.pretrain-8k"
CONFIG = "mellum2-12b-a2.5b-train1"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# metric files this configuration brings; their entries wait for the
# manifest's set-up metrics to be found by membership (PERF.md section 7)
NEW_METRICS = (
    "kernels.flash_attention_window_fwd_roofline",
    "kernels.flash_attention_window_bwd_roofline",
    "kernels.flash_attention_window.time_share",
    "kernels.grouped_matmul_roofline.mellum2",
    "kernels.flash_attention_window.visited_over_required")
ms = 1e-3


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def published():
    return run_mod.load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def tiny(dtype="float32"):
    """One period (three sliding layers, one full) at toy widths, the
    second of four expert-parallel ranks, float32 (the program and the
    reference then agree to rounding; what bf16 does at the published
    widths is the chip's business)."""
    cfg = published()
    cfg.update(hidden_size=32, moe_intermediate_size=16,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=256, num_experts=4, sliding_window=12,
               initializer_range=0.3, max_position_embeddings=256,
               torch_dtype=dtype)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["expert_parallel"] = {"ranks": 4, "rank": 1}
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size and dtype: above what the sound program reads
    # here, below every control and fault
    cfg["limits"] = {"loss3_gap": 2e-5, "grad1_worst_leaf_gap": 1e-3,
                     "grad1_median_leaf_gap": 1e-4,
                     "change_worst_leaf_gap": 1e-3, "held_rows_drift": 0.5}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    traffic.update(seq_len=40, rows=4096)
    cell = {"name": "tiny.mellum2", "config": "tiny",
            "traffic": "pretrain-8k", "chips": 1}
    return cell, cfg, traffic


TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"}]}
SEED = 2**31 + 11


def _run(seed=SEED, trace=0):
    return run_mod.run_cell(TINY_MANIFEST, *tiny(), seed, 1.0, trace,
                            require_chip=False)


@pytest.fixture(scope="module")
def sound():
    """One run of the runner, shared: (line, Run)."""
    mp = pytest.MonkeyPatch()
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", os.path.join(d, "cache"))
        try:
            yield _run()
        finally:
            mp.undo()


# ------------------------------------------------- model against reference
def test_leaf_list_is_the_models_own_names_and_shapes():
    from benchmarks import train_mellum2

    _, cfg, _ = tiny()
    model = train_mellum2.build_model(cfg, 3)
    named = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert named == [(n, s) for n, s, _ in W.leaf_specs(cfg)]
    shapes = dict(named)
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (32, 4 * 16)
    assert shapes["model.layers.0.self_attn.k_proj.weight"] == (32, 2 * 16)
    # the router keeps all 16 outputs, 4 experts are held, no shared expert
    assert shapes["model.layers.3.mlp.gate.weight"] == (32, 16)
    assert shapes["model.layers.3.mlp.experts.w_down"] == (4, 16, 32)
    assert not [n for n in shapes if "shared" in n]
    layers = model.model.layers
    assert [layer.self_attn.window for layer in layers] == [12, 12, 12, None]
    assert all(layer.mlp.held == (4, 4) for layer in layers)


def test_the_program_agrees_with_the_reference_leaf_by_leaf(sound):
    """Seeded weights, three AdamW steps: each step's loss, every leaf's
    first gradient and every leaf's change, not the worst leaf alone."""
    _, run = sound
    got, ref = run.kept["got"], run.kept["ref"]
    for a, b in zip(got["losses"], ref["losses"]):
        assert a == pytest.approx(b, rel=2e-6)
    assert set(got["grad_norms"]) == {n for n, _, _ in W.leaf_specs(
        run.config)}
    for key, tol in (("grad_norms", 1e-4), ("change_norms", 3e-4)):
        median = statistics.median(ref[key].values())
        for name, want in ref[key].items():
            gap = abs(got[key][name] - want) / max(want, median)
            assert gap < tol, (key, name, got[key][name], want)
    assert min(ref["grad_norms"].values()) > 0
    assert min(ref["change_norms"].values()) > 0


def test_seeded_leaves_have_their_kinds():
    _, cfg, _ = tiny("bfloat16")
    made = W.make_weights(cfg, 5)
    again = W.make_weights(cfg, 5)
    other = W.make_weights(cfg, 6)
    key, std = W.seed_key(5), cfg["initializer_range"]
    for i, (name, shape, kind) in enumerate(W.leaf_specs(cfg)):
        a = np.asarray(made[name].astype("float32"))
        assert a.shape == shape
        np.testing.assert_array_equal(
            a, np.asarray(again[name].astype("float32")))
        one = W.make_leaf(key, index=i, shape=shape, kind=kind, std=std,
                          dtype=made[name].dtype)
        np.testing.assert_array_equal(a, np.asarray(one.astype("float32")))
        if kind == "ones":
            assert (a == 1).all() and "norm" in name
        else:
            assert (a != np.asarray(other[name].astype("float32"))).any()
            assert abs(a.std() - std) < 0.25 * std


# ----------------------------------------------------------------- the runner
def test_runner_follows_the_control_flow(sound):
    line, run = sound
    json.dumps(line)
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]
    assert {"loss3_gap", "grad1_worst_leaf_gap", "grad1_median_leaf_gap",
            "change_worst_leaf_gap", "held_rows_drift", "compiles_in_window",
            "failed_steps", "fed_rows_differ"} == set(line["checks"])
    assert any(n.startswith("expert_load, rows a layer") for n in
               line["notes"])
    c = run.counts
    steps = line["attempted"]
    assert c["steps"] == steps
    assert c["tokens_per_chip"] == 2 * 40 * steps
    assert c["required_flops"] == pytest.approx(
        work.train_flops_per_token(run.config, 40) * 2 * 40 * steps)
    ops, nbytes = work.window_core_work(run.config, 2 * steps, 40)
    assert (c["window_fwd_flops"], c["window_fwd_bytes"]) == (ops, nbytes)
    assert c["window_bwd_flops"] == 2 * ops
    # four expert layers, 4 of 16 experts held, 8 experts a token
    assert c["moe_assignments"] == 2 * 40 * 8 * 4 * steps
    assert 0.1 < c["moe_held_rows"] / c["moe_assignments"] < 0.5
    assert c["gmm_fwd_flops"] == 3 * 2 * c["moe_held_rows"] * 32 * 16
    # at 40 tokens the sliding layers take the math path: no tile recorded
    assert "window_visited_pairs" not in c


def test_runner_records_the_tiles_the_program_chose(sound, monkeypatch):
    from benchmarks import train_mellum2 as T
    from paddle_tpu.kernels.pallas import _compat

    _, run = sound
    before = {(T.WINDOW_FWD, 128, 128): 3.0}
    monkeypatch.setattr(_compat, "flash_blocks", lambda: {
        ("flash_attention_fwd", 1024, 1024): 4.0,
        (T.WINDOW_FWD, 128, 128): 3.0, (T.WINDOW_FWD, 1024, 1024): 3.0})
    # a tile some earlier trace of the process recorded is not this run's
    assert T.window_tiles(before) == (1024, 1024)
    counts = dict(run.counts)
    judged = run_mod.Run(run.cell, dict(run.config, sliding_window=1024),
                         run.traffic, SEED, 0.0, False)
    judged.attempted = 1
    T.record_work(judged, judged.config, 8192, 2, np.ones((4, 4)),
                  np.ones(4), T.window_tiles(before))
    c = judged.counts
    assert c["window_visited_pairs"] / c["window_required_pairs"] == (
        pytest.approx(1.4999, abs=1e-3))
    r = R.Readings({}, c, {"config": judged.config, "chips": 1}, PEAKS)
    assert R.reduce_metric(_spec(NEW_METRICS[4]), r) == pytest.approx(
        1.4999, abs=1e-3)
    assert T.window_tiles(T.recorded_tiles()) is None
    monkeypatch.setattr(_compat, "flash_blocks", lambda: {
        ("flash_attention_fwd", 1024, 1024): 4.0})
    assert T.window_tiles({}) is None
    assert counts == run.counts


def test_held_rows_drift_is_between_the_windows_halves(sound):
    from benchmarks import train_mellum2 as T

    # rows [steps, layers]: the second half's mean less the first's over
    # the window's mean, the widest layer's; a layer left by the window's
    # middle reads 2, one sent nothing all window long 1
    assert T.held_rows_drift([[20000], [20000], [30000], [30000]]) == 0.4
    assert T.held_rows_drift([[30000, 20000], [30000, 20000],
                              [30000, 0], [30000, 0]]) == 2.0
    assert T.held_rows_drift([[30000, 0], [30000, 0]]) == 1.0
    # steps that spread about an unmoved mean read little
    spread = 32768 + 8000 * np.where(np.arange(92) % 2, 1, -1)
    assert T.held_rows_drift(spread[:, None]) == 0.0
    assert T.held_rows_drift([[32768]]) == 0.0
    series = np.array([[[3, 1], [2, 2]], [[4, 2], [1, 0]]])  # steps, layers
    notes = T.router_notes(series, [np.ones((2, 4))] * 2,
                           [np.ones((2, 4)), 1.5 * np.ones((2, 4))])
    assert notes[0].endswith("layer 0 [4]; layer 1 [4]")
    assert notes[1].endswith("[0.2, 0.6]")
    assert notes[2].endswith("layer 1's held experts, rows at the window's "
                             "first and last step: [2, 2] -> [1, 0]")
    assert notes[3].endswith("[0.0, 0.5]")
    line, run = sound
    # 2 x 40 tokens, 8 experts a token, 4 of 16 held: 160 rows a layer
    assert work.routed_rows_per_token(run.config) * 80 == 160
    assert 0 <= line["checks"]["held_rows_drift"]["value"] < 0.5
    assert sum(n.startswith(("held rows a layer", "routers' weights moved"))
               for n in line["notes"]) == 3


def test_runner_reports_its_metrics_from_its_counts(sound):
    """On the chip the metrics read these counts; here, with peaks
    supplied by hand, the reducers give numbers and none raises."""
    _, run = sound
    r = R.Readings(run.series, run.counts, {"config": run.config,
                                            "chips": 1}, PEAKS)
    assert R.reduce_metric(_spec("train_step.mfu"), r) > 0
    assert R.reduce_metric(_spec("train_step.input_wait_ms"), r) >= 0
    assert R.reduce_metric(_spec("train_tokens_per_s_per_chip"), r) > 0
    # no trace, no tile: every new metric is left out, not raised
    for m in NEW_METRICS:
        assert R.reduce_metric(_spec(m), r) is None


@pytest.mark.parametrize("what", [
    "control_fp8", "fault_unchanged_state", "fault_half_batch",
    "fault_window_left_out", "fault_yarn_left_out"])
def test_each_control_and_fault_comes_out_not_correct(sound, what):
    """The reference in the program's place with one control or fault
    planted, judged by the harness's own comparison as
    `train_mellum2.py limits` judges it on the chip."""
    from benchmarks import train_mellum2 as T

    _, run = sound
    cell, cfg, traffic = tiny()
    assert what in dict(T.CONTROLS)
    got = T.control_readings(cfg, SEED, run.kept["fed"], what)
    judged = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    T.compare(judged, got, run.kept["ref"], cfg["limits"])
    assert not judged.correct(), judged.checks
    same = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    T.compare(same, run.kept["ref"], run.kept["ref"], cfg["limits"])
    assert same.correct()


def test_a_program_whose_sliding_layers_see_every_key_is_not_correct(
        monkeypatch):
    """This model's first fault planted in the program and not in the
    reference: the sliding layers run causal."""
    import paddle_tpu.ops as F

    monkeypatch.setattr(F, "window_attention", lambda q, k, v, window, **kw:
                        F.scaled_dot_product_attention(q, k, v, None, 0.0,
                                                       True))
    line, _ = _run()
    assert not line["correct"], line["checks"]


def test_a_program_without_yarn_is_not_correct(monkeypatch):
    """This model's second fault: the full layer takes plain rope."""
    import paddle_tpu.ops as F

    monkeypatch.setattr(F, "rope_qk_freqs", lambda q, k, **kw:
                        F.rope_qk(q, k, base=500000.0))
    line, _ = _run()
    assert not line["correct"], line["checks"]


def test_limits_entry_point_is_this_runners_own():
    """prove.py sends every runner not called `train` down the serving
    branch; the configuration's runner has its own `limits`."""
    from benchmarks import train_mellum2 as T

    assert published()["runner"] == "train_mellum2"
    assert [w for w, _ in T.CONTROLS] == [
        "control_fp8", "fault_unchanged_state", "fault_half_batch",
        "fault_window_left_out", "fault_yarn_left_out"]
    assert callable(T.limits) and callable(T.main)


# ------------------------------------------------------- the configuration
SLIDING, FULL = "sliding_attention", "full_attention"
# the source's published config.json, every key
CATALOG = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_configuration_holds_the_sources_keys_and_states_its_cut():
    cfg = published()
    reduced = ["num_hidden_layers", "layer_types", "mlp_layer_types",
               "num_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == reduced
    # the floors: a whole period of at least 4 layers, at least 8 experts,
    # at least an eighth of the vocabulary; here 4 chips share each layer
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 16, 98304 // 4)
    assert cfg["layer_types"] == CATALOG["layer_types"][:4]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert work.layer_kinds(cfg) == (3, 1)
    assert cfg["expert_parallel"] == {"ranks": 4, "rank": 0}
    assert cfg["vocab_parallel"] == {"slices": 4}
    assert (cfg["expert_parallel"]["ranks"] * cfg["num_experts"]
            == CATALOG["num_experts"])
    # no width is cut
    for key in ("hidden_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window", "rope_parameters"):
        assert key not in cfg["reduced"] and cfg[key] == CATALOG[key]
    for key in ("q_k_norm", "multi_token_prediction", "router_balance_term",
                "yarn", "window", "weights", "initializer_range",
                "parameter_dtype", "optimizer", "batch_per_replica",
                "recompute", "held_rows"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"loss3_gap", "grad1_worst_leaf_gap",
                                  "grad1_median_leaf_gap",
                                  "change_worst_leaf_gap", "held_rows_drift"}
    assert cfg["limits_from"] and "4 chips" in cfg["stands_for"]
    tr = cfg["train"]
    assert tr["batch_per_replica"] == 2 and tr["recompute"] is True
    assert tr["fused_loss_chunk"] == 2048
    assert tr["optimizer"]["warmup_steps"] == 2000
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    assert (traffic["seq_len"], traffic["rows"], traffic["warmup_steps"],
            traffic["followed_steps"], traffic["loader_workers"]) == (
                8192, 65536, 4, 3, 2)


def test_parameter_count_is_reckoned_from_the_leaf_list():
    cfg = published()
    sizes = {n: int(np.prod(s)) for n, s, _ in W.leaf_specs(cfg)}
    block = lambda prefix, skip=(): sum(
        v for n, v in sizes.items()
        if n.startswith(prefix) and not any(s in n for s in skip))
    assert block("model.layers.0.self_attn.") == 21_233_664
    assert sizes["model.layers.0.mlp.gate.weight"] == 147_456
    assert block("model.layers.0.mlp.experts.") == 16 * 6_193_152
    assert block("model.layers.0.") == 120_476_160
    assert sizes["model.embed_tokens.weight"] == 24576 * 2304
    assert sum(sizes.values()) == 595_153_152
    # 16 B a parameter with the gradient: 9.52 GB, 60 % of the chip's 16 GB
    assert 16 * sum(sizes.values()) == pytest.approx(9.52e9, rel=1e-3)


# ------------------------------------------------ required work, hand-worked
def test_band_and_matmul_work_hand_worked():
    cfg = published()
    # a band of 1024 over a row of 8192: 1024 x 1025 / 2 + 7168 x 1024,
    # 4.27 times fewer pairs than the triangle
    assert work.band_pairs(8192, 1024) == 7_864_832
    assert work.band_pairs(100, 1024) == 100 * 101 // 2
    assert 8192 * 8193 // 2 / work.band_pairs(8192, 1024) == pytest.approx(
        4.267, abs=1e-3)
    assert work.attention_matmul_params(cfg) == 2304 * 128 * (64 + 8)
    assert work.routed_rows_per_token(cfg) == 8 * 16 / 64
    assert work.moe_matmul_params(cfg) == 2304 * 64 + 2 * 3 * 2304 * 896
    assert work.score_flops_per_key(cfg) == 4 * 32 * 128


def test_training_flops_per_token_hand_worked():
    cfg = published()
    attn, moe = 21_233_664, 147_456 + 12_386_304
    sliding = 3 * 16384 * 7_864_832 / 8192
    full = 16384 * 8193 / 2
    head = 2 * 2304 * 24576
    fwd = 4 * 2 * (attn + moe) + sliding + full + head
    assert work.forward_flops_per_token(cfg, 8192) == pytest.approx(fwd)
    # 497.7 MFLOP a token forward: projections 170, experts 99, head 113,
    # full-layer scores 67, sliding-layer scores 47; 24.46 TFLOP a step
    assert fwd == pytest.approx(497.7e6, rel=1e-3)
    total = work.train_flops_per_token(cfg, 8192)
    assert total * 16384 == pytest.approx(24.46e12, rel=1e-3)
    assert sliding / fwd == pytest.approx(0.0947, abs=1e-3)


def test_kernel_work_hand_worked():
    cfg = published()
    ops, nbytes = work.window_core_work(cfg, 2, 8192)
    assert ops == 3 * 2 * 16384 * 7_864_832
    # a token and sliding layer: q and o of 32 heads, k and v of 4, bf16,
    # and the float32 log-sum-exp: 18,560 B
    assert nbytes == 3 * 16384 * (2 * 4096 + 2 * 2 * 512 + 2 * 4096 + 128)
    # compute-bound on a v5e: 3.92 ms of operations, 1.11 of bytes
    assert ops / PEAKS["bf16_flops"] > 3 * nbytes / PEAKS["hbm_bytes_per_s"]
    bops, bbytes = work.window_core_work(cfg, 2, 8192, backward=True)
    assert bops == 2 * ops and bbytes == 2 * nbytes + 3 * 16384 * (
        2 * 4096 + 2 * 2 * 512)
    ops, nbytes = work.grouped_matmul_work(cfg, 32768, 4)
    assert ops == 3 * 2 * 32768 * 2304 * 896
    assert nbytes == 4 * 2 * 3 * 16 * 2304 * 896 + 2 * 32768 * (
        2 * (2304 + 896) + 896 + 2304)


# ------------------------------------------- kernel files against instructions
def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _call(name, operands="%bitcast.4"):
    return (f"%{name} = bf16[64,8192,128]{{2,1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


def _trace():
    """A step of 100 ms, busy throughout: the window forward 3 x 4 ms,
    its backward 3 x (4 + 5) ms, the causal kernels of the full layer,
    the grouped matmuls, and a bystander that names a window kernel among
    its operands."""
    ops = []
    at = 0.0
    for i in range(3):
        for name, dur in ((f"flash_attention_window_fwd.{i}", 4),
                          (f"flash_attention_window_bwd_dq.{i}", 4),
                          (f"flash_attention_window_bwd_dkv.{i}", 5)):
            ops.append((at * ms, (at + dur) * ms, _call(name), ""))
            at += dur
    for name, dur in (("flash_attention_fwd.2", 10),
                      ("flash_attention_bwd_dq.2", 10),
                      ("flash_attention_bwd_dkv.2", 11),
                      ("grouped_matmul.3", 2), ("grouped_matmul_dlhs.1", 1),
                      ("grouped_matmul_drhs.1", 2)):
        ops.append((at * ms, (at + dur) * ms, _call(name), ""))
        at += dur
    ops.append((at * ms, 100 * ms, "%fusion.7 = bf16[2,8192,4096] fusion("
                "bf16[64,8192,128] %flash_attention_window_fwd.1), "
                "kind=kLoop", ""))
    lines = {0: {"ops": ops, "modules": [(0.0, 100 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": published()},
                      PEAKS, trace=trace)


WORK = {"window_fwd_flops": 197e12 * 6e-3, "window_fwd_bytes": 819e9 * 1e-3,
        "window_bwd_flops": 197e12 * 12e-3,
        "window_bwd_bytes": 819e9 * 2e-3,
        "gmm_fwd_flops": 197e12 * 0.5e-3, "gmm_fwd_bytes": 819e9 * 0.2e-3,
        "gmm_bwd_flops": 197e12 * 1e-3, "gmm_bwd_bytes": 819e9 * 0.4e-3}


def test_new_kernels_are_found_by_their_own_names():
    r = _readings(_trace(), **WORK)
    value = lambda m: R.reduce_metric(_spec(m), r)
    assert value("kernels.flash_attention_window.time_share") == (
        pytest.approx(3 * 13))
    assert value("kernels.flash_attention_window_fwd_roofline") == (
        pytest.approx(100 * 6 / 12))
    assert value("kernels.flash_attention_window_bwd_roofline") == (
        pytest.approx(100 * 12 / 27))
    assert value("kernels.grouped_matmul_roofline.mellum2") == (
        pytest.approx(100 * 1.5 / 5))
    # the accepted causal patterns still read the full layer's kernels
    # alone: 10 ms forward, 21 ms backward
    fwd = R._matching(r.trace, R._kernel("flash_attention_fwd").PROGRAMS,
                      R._kernel("flash_attention_fwd").OPS)
    assert sum(e - s for s, e in fwd) == pytest.approx(10 * ms)
    bwd = R._matching(r.trace, R._kernel("flash_attention_bwd").PROGRAMS,
                      R._kernel("flash_attention_bwd").OPS)
    assert sum(e - s for s, e in bwd) == pytest.approx(21 * ms)


@pytest.mark.parametrize("kernel,matches,not_matches", [
    ("flash_attention_window_fwd",
     ["%flash_attention_window_fwd.12 = ",
      "%jvp_flash_attention_window_fwd_.1 = "],
     ["%flash_attention_window_bwd_dq.6 = ", "%flash_attention_fwd.2 = ",
      "%fusion.7 = f32[1] fusion(%flash_attention_window_fwd.12)"]),
    ("flash_attention_window_bwd",
     ["%flash_attention_window_bwd_dq.6 = ",
      "%flash_attention_window_bwd_dkv.6 = "],
     ["%flash_attention_window_fwd.12 = ", "%flash_attention_bwd_dq.1 = ",
      "%mla_attention_bwd_dq.1 = "]),
    ("flash_attention_window",
     ["%flash_attention_window_fwd.12 = ",
      "%flash_attention_window_bwd_dq.6 = ",
      "%flash_attention_window_bwd_dkv.6 = "],
     ["%convert.1 = f32[2] convert(%flash_attention_window_bwd_dq.6)",
      "%flash_attention_fwd.2 = ", "%flash_attention_bwd_dkv.2 = "]),
])
def test_kernel_patterns_are_anchored_and_apart_from_the_causal_ones(
        kernel, matches, not_matches):
    k = R._kernel(kernel)
    assert re.search(k.PROGRAMS, "jit_staged")
    for text in matches:
        assert re.search(k.OPS, text), text
    for text in not_matches:
        assert not re.search(k.OPS, text), text
    # the causal kernels' own anchored patterns do not take the window's
    for causal in ("flash_attention_fwd", "flash_attention_bwd",
                   "flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        for text in matches:
            assert not re.search(R._kernel(causal).OPS, text)


def test_a_program_without_the_kernels_reads_nothing():
    """The parent commit has no such kernels and records no such counts:
    every new metric is left out of its line, none raises."""
    ops = [(0.0, 50 * ms, "%fusion.1 = bf16[8] fusion(%p.1)", "")]
    bare = R.summarize_events(
        {0: {"ops": ops, "modules": [(0.0, 50 * ms, "jit_staged")]}}, [],
        set())
    for readings in (_readings(bare, **WORK), _readings()):
        for name in NEW_METRICS:
            assert R.reduce_metric(_spec(name), readings) is None, name
    # the kernels without the counts: the shares of a roofline are left
    # out, a time share needs no count
    for name in NEW_METRICS[:4]:
        value = R.reduce_metric(_spec(name), _readings(_trace()))
        assert (value is None) == ("roofline" in name), name


def test_the_cell_has_its_entries_and_its_metric_files():
    """By membership, never by position. The cell reports the two
    training metrics every training cell reports; the new metrics' files
    stand ready, and their entries are left out of the manifest (an entry
    after the ten set-up metrics is what the accepted
    test_startup_metrics.py refuses: PERF.md section 7)."""
    for name in NEW_METRICS:
        spec = _spec(name)
        assert set(spec) == {"reducer", "args", "reads"}
        assert spec["reducer"] in R.REDUCERS
    twin = _spec("kernels.grouped_matmul_roofline")
    assert (_spec(NEW_METRICS[3])["reducer"], _spec(NEW_METRICS[3])["args"]
            ) == (twin["reducer"], twin["args"])
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-8k", 1)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in MANIFEST["configs"]}[CONFIG]
    assert entry["reduced"] == published()["reduced"]
    assert entry["source"] == published()["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run_mod.cell_metrics(MANIFEST, cell, g)}
    assert reported == {"train_tokens_per_s_per_chip", "setup_s",
                        "train_step.mfu", "train_step.input_wait_ms"}
    for group, name in (("end_to_end", "train_tokens_per_s_per_chip"),
                        ("per_layer", "train_step.mfu"),
                        ("per_layer", "train_step.input_wait_ms")):
        entry = next(m for m in MANIFEST[group] if m["name"] == name)
        assert CELL in entry["workloads"]
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert len(names) == len(set(names))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
