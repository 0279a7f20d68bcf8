"""The Qwen3-Next configuration's benchmark files on the CPU at a tiny
size: the model against the plain reference (loss, every leaf's gradient,
the parameters after three AdamW steps), the runner's control flow, each
control and planted fault coming out not correct (this model's own, the
held experts' part left out, among them), the arithmetic of
work_qwen3_next.py against hand-worked values, and the new kernel files'
patterns against instruction texts.
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

from benchmarks import reduce as R                    # noqa: E402
from benchmarks import run as run_mod                 # noqa: E402
from benchmarks import weights_qwen3_next as W        # noqa: E402
from benchmarks import work_qwen3_next as work        # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "qwen3next.pretrain-8k"
CONFIG = "qwen3-next-80b-a3b-train1"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
ms = 1e-3


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def published():
    return run_mod.load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def tiny(dtype="float32"):
    """One period at toy widths; float32, so that the program and the
    reference choose the same experts and agree to rounding (in bf16 a
    toy router flips an expert for a token in twenty and a leaf's
    gradient moves by tens of percent: that is the chip's business, at
    the published widths)."""
    cfg = published()
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=4, vocab_size=256,
               initializer_range=0.1, max_position_embeddings=256,
               torch_dtype=dtype)
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["expert_parallel"] = {"ranks": 4, "rank": 1}
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size and dtype: above what the sound program reads
    # here (2e-7, 2e-5, 3e-5), below every control and fault
    cfg["limits"] = {"loss3_gap": 2e-5, "grad1_worst_leaf_gap": 1e-3,
                     "grad1_median_leaf_gap": 1e-4,
                     "change_worst_leaf_gap": 1e-3, "held_rows_drift": 0.5}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    traffic.update(seq_len=96, rows=4096)
    cell = {"name": "tiny.qwen3next", "config": "tiny",
            "traffic": "pretrain-8k", "chips": 1}
    return cell, cfg, traffic


TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"},
                  {"name": "moe.held_assignment_share", "unit": "%"}]}
SEED = 2**31 + 7


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
    from benchmarks import train_hybrid

    _, cfg, _ = tiny()
    model = train_hybrid.build_model(cfg, 3)
    named = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert named == [(n, s) for n, s, _ in W.leaf_specs(cfg)]
    # the router keeps the published width, 4 of 16 experts are held,
    # those of rank 1
    assert dict(named)["model.layers.0.mlp.gate.weight"] == (64, 16)
    assert dict(named)["model.layers.0.mlp.experts.w_up"] == (4, 64, 32)
    assert model.model.layers[0].mlp.held == (4, 4)


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
    # every leaf took part: no gradient is nought, every leaf moved
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
        if kind == "zeros":
            assert (a == 0).all()
        elif kind == "ones":
            assert (a == 1).all()
        elif kind == "a_log":
            assert (np.exp(a) > 0).all() and (np.exp(a) < 16.1).all()
        else:
            assert (a != np.asarray(other[name].astype("float32"))).any()
            assert abs(a.std() - std) < 0.25 * std


# ----------------------------------------------------------------- the runner
def test_hybrid_runner_follows_the_control_flow(sound):
    line, run = sound
    json.dumps(line)
    assert list(line)[-1] == "checks"
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["metrics"] == {} and "breakdown" not in line
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"], line["checks"]
    assert {"loss3_gap", "grad1_worst_leaf_gap", "grad1_median_leaf_gap",
            "change_worst_leaf_gap", "compiles_in_window", "failed_steps",
            "fed_rows_differ", "held_rows_drift"} == set(line["checks"])
    assert any(n.startswith("loss1_gap") for n in line["notes"])
    c = run.counts
    assert c["steps"] == line["attempted"]
    assert c["tokens_per_chip"] == 2 * 96 * c["steps"]
    # one layer in four attends: work.flash_train_flops multiplies by all
    assert c["flash_sequences"] == 2 * c["steps"] / 4
    assert c["required_flops"] == pytest.approx(
        work.train_flops_per_token(run.config, 96) * 2 * 96 * c["steps"])
    # 4 of 16 experts held: about a quarter of the assignments, summed
    # over the layers and over the window's steps (not the last step's)
    assert c["moe_assignments"] == 2 * 96 * 4 * 4 * c["steps"]
    assert 0.15 < c["moe_held_rows"] / c["moe_assignments"] < 0.35
    assert 1.0 <= c["moe_load_max_over_mean"] <= 4.0
    # the grouped products are counted at the rows they were sent
    assert c["gmm_fwd_flops"] == 3 * 2 * c["moe_held_rows"] * 64 * 32
    assert c["gmm_bwd_flops"] == 2 * c["gmm_fwd_flops"]
    note = next(n for n in line["notes"] if n.startswith("expert_load"))
    assert "after the warm-up steps" in note and "after the last" in note


def test_a_router_that_leaves_inside_the_window_is_not_correct(sound):
    """The cell's traffic is the router's load: at a constant 3e-4 the
    chip's last layer went from 20,865 rows to 0 and its third from
    20,721 to 21,698 (PERF.md section 6); under the warm-up a layer moves
    by a per cent or two."""
    from benchmarks import train_hybrid

    assert train_hybrid.rows_drift(
        [19673, 20510, 20721, 20865], [20399, 20188, 21698, 0]) == 1.0
    assert train_hybrid.rows_drift(
        [20000, 21000], [20300, 20800]) == pytest.approx(0.015)
    limit = published()["limits"]["held_rows_drift"]
    assert 0.015 < limit < 1.0
    line, _ = sound
    assert 0 <= line["checks"]["held_rows_drift"]["value"] < 0.5


def test_hybrid_runner_reports_its_counter_metrics(sound):
    """On the chip the two moe metrics read these counts; here, with
    peaks supplied by hand, the reducers give the same numbers."""
    _, run = sound
    r = R.Readings(run.series, run.counts, {"config": run.config,
                                            "chips": 1}, PEAKS)
    share = R.reduce_metric(_spec("moe.held_assignment_share"), r)
    assert share == pytest.approx(
        100 * run.counts["moe_held_rows"] / run.counts["moe_assignments"])
    assert R.reduce_metric(_spec("moe.expert_load_imbalance"), r) == (
        run.counts["moe_load_max_over_mean"])
    assert R.reduce_metric(_spec("train_step.mfu"), r) > 0


@pytest.mark.parametrize("what", ["control_fp8", "fault_unchanged_state",
                                  "fault_half_batch",
                                  "fault_held_experts_left_out"])
def test_each_control_and_fault_comes_out_not_correct(sound, what):
    """The reference in the program's place with one control or fault
    planted, judged by the harness's own comparison as
    `train_hybrid.py limits` judges it on the chip."""
    from benchmarks import train, train_hybrid

    _, run = sound
    cell, cfg, traffic = tiny()
    assert what in dict(train_hybrid.CONTROLS)
    got = train_hybrid.control_readings(cfg, SEED, run.kept["fed"], what)
    judged = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    train_hybrid.compare(judged, got, run.kept["ref"], cfg["limits"])
    assert not judged.correct(), judged.checks
    same = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    train_hybrid.compare(same, run.kept["ref"], run.kept["ref"],
                         cfg["limits"])
    assert same.correct()


def test_a_precision_lost_everywhere_shows_at_the_median_leaf(sound):
    """The fp8 control is caught by the first gradient's median leaf, a
    number that does not hang on the one leaf that reads worst."""
    from benchmarks import train_hybrid

    _, run = sound
    _, cfg, _ = tiny()
    ref = run.kept["ref"]["grad_norms"]
    low = train_hybrid.control_readings(
        cfg, SEED, run.kept["fed"], "control_fp8")["grad_norms"]
    sound_gap = train_hybrid.median_leaf_gap(run.kept["got"]["grad_norms"],
                                             ref)
    assert sound_gap < cfg["limits"]["grad1_median_leaf_gap"] < (
        train_hybrid.median_leaf_gap(low, ref))
    # hand-worked: gaps 0, 0.1 and 0.5 of leaves at or above the median
    assert train_hybrid.median_leaf_gap(
        {"a": 1.0, "b": 2.2, "c": 6.0}, {"a": 1.0, "b": 2.0, "c": 4.0}
    ) == pytest.approx(0.1)
    # a small leaf's gap is taken over the median leaf's norm
    assert train_hybrid.median_leaf_gap(
        {"a": 0.2, "b": 2.0, "c": 4.0}, {"a": 0.1, "b": 2.0, "c": 4.0}
    ) == 0.0


def test_the_learning_rate_warms_up_as_the_configuration_says():
    """Step t runs at learning_rate * t / warmup_steps, in the reference
    as in the runner (which the leaf-by-leaf comparison holds to it)."""
    from benchmarks import train
    from benchmarks.reference import qwen3_next as reference

    _, cfg, traffic = tiny()
    opt = cfg["train"]["optimizer"]
    assert (opt["learning_rate"], opt["warmup_steps"]) == (3e-4, 2000)
    fed = train.followed_batches(cfg, traffic, SEED)[:2]
    warm = reference.train_steps(cfg, SEED, fed, opt, dtype="float32")
    flat = reference.train_steps(
        cfg, SEED, fed, dict(opt, warmup_steps=0), dtype="float32")
    name = "model.layers.0.mlp.experts.w_up"
    # two steps at 1/2000 and 2/2000 of the rate against two at the rate
    ratio = warm["change_norms"][name] / flat["change_norms"][name]
    assert 1 / 2000 < ratio < 2 / 2000
    assert warm["grad_norms"] == flat["grad_norms"]


def test_a_program_that_leaves_its_experts_out_is_not_correct(monkeypatch):
    """This model's own fault planted in the program: the routed experts'
    part dropped, the shared expert kept."""
    from paddle_tpu import ops as F

    def nothing(x, *args, **kwargs):
        return F.zeros(list(x.shape), x.dtype)

    monkeypatch.setattr(F, "moe_held_experts", nothing)
    line, _ = _run()
    assert not line["correct"], line["checks"]
    assert line["checks"]["grad1_worst_leaf_gap"]["value"] > 0.5


def test_limits_entry_point_is_this_runners_own():
    """prove.py sends every runner not called `train` down the serving
    branch; the configuration's runner has its own `limits`."""
    from benchmarks import train_hybrid

    assert published()["runner"] == "train_hybrid"
    assert [w for w, _ in train_hybrid.CONTROLS] == [
        "control_fp8", "fault_unchanged_state", "fault_half_batch",
        "fault_held_experts_left_out"]
    assert callable(train_hybrid.limits) and callable(train_hybrid.main)


# ------------------------------------------------------- the configuration
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_configuration_holds_the_sources_keys_and_states_its_cut():
    cfg = published()
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 151936 // 8)
    # the floors of a model_config cut: a whole period, 8 experts, an
    # eighth of the vocabulary
    assert cfg["num_hidden_layers"] % cfg["full_attention_interval"] == 0
    assert cfg["expert_parallel"]["ranks"] * cfg["num_experts"] == 512
    for key in ("multi_token_prediction", "router_balance_term", "weights"):
        assert key in cfg["assumed"]
    assert set(cfg["limits"]) == {"loss3_gap", "grad1_worst_leaf_gap",
                                  "grad1_median_leaf_gap",
                                  "change_worst_leaf_gap", "held_rows_drift"}
    # the router's load may not leave the stated share inside the window
    assert 0.05 <= cfg["limits"]["held_rows_drift"] <= 0.19
    assert cfg["limits_from"] and cfg["stands_for"]
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    assert (traffic["seq_len"], traffic["rows"], traffic["warmup_steps"],
            traffic["followed_steps"], traffic["loader_workers"]) == (
                8192, 65536, 4, 3, 2)
    assert cfg["train"]["batch_per_replica"] in (4, 2)


def test_parameter_count_is_the_issues_arithmetic():
    cfg = published()
    n = sum(int(np.prod(s)) for _, s, _ in W.leaf_specs(cfg))
    # 3 DeltaNet + 1 attention mixers, 4 expert layers, embedding + head
    assert n == pytest.approx(625.7e6, rel=2e-3)
    assert 14 * n == pytest.approx(8.76e9, rel=2e-3)    # resident bytes


# ------------------------------------------------ required work, hand-worked
def test_matmul_params_hand_worked():
    cfg = published()
    # qkvz 2048 x (2048 + 2048 + 4096 + 4096), ba 2048 x 64, out 4096 x 2048
    assert work.deltanet_matmul_params(cfg) == (
        2048 * 12288 + 2048 * 64 + 4096 * 2048)
    # q with its gate 2048 x 8192, k and v 2048 x 512 each, o 4096 x 2048
    assert work.attention_matmul_params(cfg) == (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048)
    assert work.routed_rows_per_token(cfg) == 10 * 32 / 512 == 0.625
    # router 2048 x 512, shared expert 3 x 2048 x 512 and its gate, and
    # 0.625 experts of 3 x 2048 x 512
    assert work.moe_matmul_params(cfg) == (
        2048 * 512 + 3 * 2048 * 512 + 2048 + 0.625 * 3 * 2048 * 512)
    assert work.layer_kinds(cfg) == (3, 1)
    assert work.recurrence_flops_per_token(cfg) == 32 * 6 * 128 * 128
    assert work.conv_flops_per_token(cfg) == 2 * 4 * 8192


def test_training_flops_per_token_hand_worked():
    cfg = published()
    matmuls = 2 * (3 * 33_685_504 + 27_262_976 + 4 * 6_162_432
                   + 2048 * 18992)
    attention = 4 * 16 * 256 * 8193 / 2
    recurrence = 3 * (32 * 6 * 128 * 128 + 2 * 4 * 8192)
    fwd = matmuls + attention + recurrence
    assert work.forward_flops_per_token(cfg, 8192) == pytest.approx(fwd)
    assert fwd == pytest.approx(461e6, rel=5e-3)          # the issue's
    assert work.train_flops_per_token(cfg, 8192) == pytest.approx(3 * fwd)
    # a step of 32,768 tokens: 45 TFLOP
    assert 3 * fwd * 32768 == pytest.approx(45.3e12, rel=5e-3)


def test_kernel_work_hand_worked():
    cfg = published()
    ops, nbytes = work.delta_rule_work(cfg, 1000)
    assert ops == 3 * 1000 * 32 * 6 * 128 * 128
    # a token and layer: q, k (2048 each) and v (4096) bf16 in, o (4096)
    # bf16 out, g and beta 32 float32 each
    assert nbytes == 3 * 1000 * (2 * 8192 + 2 * 4096 + 2 * 4 * 32)
    assert work.delta_rule_work(cfg, 1000, backward=True) == (
        2 * ops, 2 * nbytes)
    # 2,560 rows sent to the experts in 4 calls of a layer (640 a call:
    # what a uniform router sends at 1,024 tokens)
    assert work.routed_rows_per_token(cfg) * 1024 == 640
    ops, nbytes = work.grouped_matmul_work(cfg, 2560, 4)
    assert ops == 3 * 2 * 2560 * 2048 * 512
    assert nbytes == (4 * 2 * 3 * 32 * 2048 * 512
                      + 2 * 2560 * (2 * (2048 + 512) + 512 + 2048))
    assert work.grouped_matmul_work(cfg, 2560, 4, backward=True) == (
        2 * ops, 2 * nbytes)
    # a layer that was sent nothing still reads its weights
    assert work.grouped_matmul_work(cfg, 0, 4) == (
        0, 4 * 2 * 3 * 32 * 2048 * 512)


# ------------------------------------------- kernel files against instructions
def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _call(name, operands="%bitcast.4"):
    return (f"%{name} = bf16[4,8192,4096]{{2,1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


def _trace():
    """A step of 100 ms, busy throughout: delta rule forward 2 x 6 ms
    (the layer is rematerialised), backward 20 ms, grouped products 4 + 3
    + 5 ms, and bystanders that name a kernel among their operands."""
    ops = [
        (0 * ms, 6 * ms, _call("gated_delta_rule_fwd.9"), ""),
        (6 * ms, 10 * ms, _call("grouped_matmul.47"), ""),
        (10 * ms, 11 * ms, "%fusion.7 = bf16[4,8192,4096] fusion(bf16[4,8192,"
         "4096] %gated_delta_rule_fwd.9, %grouped_matmul.47), kind=kLoop",
         ""),
        (11 * ms, 17 * ms, _call("checkpoint_gated_delta_rule_fwd.3"), ""),
        (17 * ms, 37 * ms, _call("gated_delta_rule_bwd.2"), ""),
        (37 * ms, 40 * ms, _call("grouped_matmul_dlhs.12"), ""),
        (40 * ms, 45 * ms, _call("transpose_jvp_grouped_matmul_drhs_.1"),
         ""),
        (45 * ms, 46 * ms, "%convert.3 = f32[32,2048,512] convert("
         "bf16[32,2048,512] %grouped_matmul_drhs.12)", ""),
        (46 * ms, 100 * ms, "%convert_select_fusion.5 = bf16[8192,12288] "
         "fusion(bf16[8192] %copy-done.9), kind=kOutput", ""),
    ]
    lines = {0: {"ops": ops, "modules": [(0.0, 100 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": published()},
                      PEAKS, trace=trace)


WORK = {"gdr_fwd_flops": 197e12 * 1e-3, "gdr_fwd_bytes": 819e9 * 3e-3,
        "gdr_bwd_flops": 197e12 * 2e-3, "gdr_bwd_bytes": 819e9 * 6e-3,
        "gmm_fwd_flops": 197e12 * 2e-3, "gmm_fwd_bytes": 819e9 * 1e-3,
        "gmm_bwd_flops": 197e12 * 4e-3, "gmm_bwd_bytes": 819e9 * 2e-3}


def test_new_kernels_are_found_by_their_own_names():
    r = _readings(_trace(), **WORK)
    value = lambda m: R.reduce_metric(_spec(m), r)
    assert value("kernels.gated_delta_rule.time_share") == pytest.approx(
        6 + 6 + 20)
    assert value("kernels.grouped_matmul.time_share") == pytest.approx(
        4 + 3 + 5)
    # the forward is memory-bound here (3 ms of bytes against 1 of
    # operations), over its two calls; the backward likewise
    assert value("kernels.gated_delta_rule_fwd_roofline") == pytest.approx(
        100 * 3 / 12)
    assert value("kernels.gated_delta_rule_bwd_roofline") == pytest.approx(
        100 * 6 / 20)
    # the grouped products are compute-bound: 6 ms of operations
    assert value("kernels.grouped_matmul_roofline") == pytest.approx(
        100 * 6 / 12)


@pytest.mark.parametrize("kernel,matches,not_matches", [
    ("gated_delta_rule_fwd",
     ["%gated_delta_rule_fwd.9 = ", "%jvp_gated_delta_rule_fwd_.1 = "],
     ["%gated_delta_rule_bwd.2 = ",
      "%fusion.7 = f32[1] fusion(%gated_delta_rule_fwd.9)"]),
    ("gated_delta_rule_bwd",
     ["%gated_delta_rule_bwd.2 = ", "%gated_delta_rule_bwd_intra.4 = "],
     ["%gated_delta_rule_fwd.9 = "]),
    ("gated_delta_rule",
     ["%gated_delta_rule_fwd.9 = ", "%gated_delta_rule_bwd.2 = "],
     ["%convert.1 = f32[2] convert(%gated_delta_rule_bwd.2)"]),
    ("grouped_matmul",
     ["%grouped_matmul.47 = ", "%grouped_matmul_dlhs.12 = ",
      "%grouped_matmul_drhs.3 = ", "%jvp_grouped_matmul_.2 = "],
     ["%convert.3 = f32[2] convert(%grouped_matmul_drhs.12)",
      "%flash_attention_fwd.1 = "]),
])
def test_kernel_patterns_are_anchored_at_the_instruction(kernel, matches,
                                                         not_matches):
    k = R._kernel(kernel)
    assert re.search(k.PROGRAMS, "jit_staged")
    for text in matches:
        assert re.search(k.OPS, text), text
    for text in not_matches:
        assert not re.search(k.OPS, text), text


def test_a_program_without_the_kernels_reads_nothing():
    """The parent commit has no such kernels and records no such counts:
    every new metric is left out of its line, none raises."""
    ops = [(0.0, 50 * ms, "%fusion.1 = bf16[8] fusion(%p.1)", "")]
    bare = R.summarize_events(
        {0: {"ops": ops, "modules": [(0.0, 50 * ms, "jit_staged")]}}, [],
        set())
    for readings in (_readings(bare, **WORK), _readings(_trace()),
                     _readings()):
        for m in MANIFEST["per_layer"]:
            if m.get("workloads") == [CELL]:
                value = R.reduce_metric(_spec(m["name"]), readings)
                if readings.trace is bare or readings.trace is None or (
                        "roofline" in m["name"]) or m["name"].startswith(
                            "moe."):
                    assert value is None, m["name"]


def test_new_metrics_have_their_entries_and_files():
    mine = [m for m in MANIFEST["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "kernels.gated_delta_rule_fwd_roofline",
        "kernels.gated_delta_rule_bwd_roofline",
        "kernels.gated_delta_rule.time_share",
        "kernels.grouped_matmul_roofline",
        "kernels.grouped_matmul.time_share",
        "moe.held_assignment_share", "moe.expert_load_imbalance"]
    for m in mine:
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert set(_spec(m["name"])) == {"reducer", "args", "reads"}
    cell = {w["name"]: w for w in MANIFEST["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-8k", 1)
    reported = {m["name"] for g in ("end_to_end", "per_layer")
                for m in run_mod.cell_metrics(MANIFEST, cell, g)}
    assert {"train_tokens_per_s_per_chip", "setup_s", "train_step.mfu",
            "train_step.input_wait_ms"} <= reported
    # tests/benchmarks/test_program_metrics.py pins the twelve metrics of
    # PR 25 to their one cell (`workloads == [CELL]`), and the unanchored
    # pair stays with the cell it was accepted in
    assert not any(n.startswith(("kernels.flash_attention", "loader.",
                                 "setup.", "train_step.host_ms"))
                   for n in reported)
