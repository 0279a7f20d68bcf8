"""The kanana-2-30b-a3b configuration's benchmark files on the CPU at a
tiny size: the configuration against the catalog row's widths, the leaf
list against the model's creation order, the parameter count, the runner's
control flow with the program against the plain reference (each step's
loss, every leaf's first gradient, every leaf's change after three AdamW
steps), each control and planted fault coming out not correct (this
model's own, the routed experts and the rotary score left out, among
them), the arithmetic of work_deepseek_v3.py against hand-worked values,
and the new kernel files' patterns against instruction texts.

Entries of BENCHMARK.json are found by membership (`CELL in
entry["workloads"]`), never by position or by `== [CELL]`: the next
configuration is added after this one without touching this file.
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
from benchmarks import weights_deepseek_v3 as W           # noqa: E402
from benchmarks import work_deepseek_v3 as work           # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "kanana2.pretrain-8k"
CONFIG = "kanana-2-30b-a3b-train1"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = (
    "kernels.mla_attention_fwd_roofline", "kernels.mla_attention_bwd_roofline",
    "kernels.mla_attention.time_share",
    "kernels.grouped_matmul_roofline.kanana2",
    "kernels.grouped_matmul.time_share.kanana2",
    "moe.held_assignment_share.kanana2", "moe.expert_load_imbalance.kanana2")
ms = 1e-3


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def published():
    return run_mod.load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def tiny(dtype="float32"):
    """The leading dense layer and two expert layers at toy widths, the
    second of four expert-parallel ranks, float32 (the program and the
    reference then agree to rounding; what bf16 does at the published
    widths is the chip's business)."""
    cfg = published()
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=16,
               qk_nope_head_dim=16, qk_rope_head_dim=8, qk_head_dim=24,
               v_head_dim=16, head_dim=8, vocab_size=256,
               num_hidden_layers=3, n_routed_experts=4,
               initializer_range=0.3, max_position_embeddings=256,
               torch_dtype=dtype)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["expert_parallel"] = {"ranks": 4, "rank": 1}
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size and dtype: above what the sound program reads
    # here (7e-8, 9e-7, 2e-7, 3e-6), below every control and fault
    cfg["limits"] = {"loss3_gap": 2e-5, "grad1_worst_leaf_gap": 1e-3,
                     "grad1_median_leaf_gap": 1e-4,
                     "change_worst_leaf_gap": 1e-3, "held_rows_drift": 0.5}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    traffic.update(seq_len=40, rows=4096)
    cell = {"name": "tiny.kanana2", "config": "tiny",
            "traffic": "pretrain-8k", "chips": 1}
    return cell, cfg, traffic


TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"}]}
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
    from benchmarks import train_deepseek_v3

    _, cfg, _ = tiny()
    model = train_deepseek_v3.build_model(cfg, 3)
    named = [(n, tuple(p.shape)) for n, p in model.named_parameters()]
    assert named == [(n, s) for n, s, _ in W.leaf_specs(cfg)]
    shapes = dict(named)
    # the source's names and layouts: per head nope | rope, latent | the
    # one rotary key, per head key | value
    assert shapes["model.layers.0.self_attn.q_proj.weight"] == (32, 4 * 24)
    assert shapes["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"] == (
        32, 16 + 8)
    assert shapes["model.layers.0.self_attn.kv_b_proj.weight"] == (16, 4 * 32)
    assert shapes["model.layers.0.mlp.gate_proj.weight"] == (32, 48)
    # the router keeps all 16 outputs, 4 experts are held, two shared
    # experts are one SwiGLU of twice the width
    assert shapes["model.layers.1.mlp.gate.weight"] == (32, 16)
    assert shapes["model.layers.1.mlp.experts.w_gate"] == (4, 32, 16)
    assert shapes["model.layers.2.mlp.shared_experts.up_proj.weight"] == (
        32, 32)
    buffers = [n for n, _ in model.named_buffers()]
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in buffers
    assert model.model.layers[1].mlp.held == (4, 4)


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
    assert any(n.startswith("loss1_gap") for n in line["notes"])
    assert any(n.startswith("expert_load, rows a layer") for n in
               line["notes"])
    c = run.counts
    steps = line["attempted"]
    assert c["steps"] == steps
    assert c["tokens_per_chip"] == 2 * 40 * steps
    assert c["required_flops"] == pytest.approx(
        work.train_flops_per_token(run.config, 40) * 2 * 40 * steps)
    ops, nbytes = work.attention_core_work(run.config, 2 * steps, 40)
    assert (c["mla_fwd_flops"], c["mla_fwd_bytes"]) == (ops, nbytes)
    assert c["mla_bwd_flops"] == 2 * ops and c["mla_bwd_bytes"] > 2 * nbytes
    # two expert layers, 4 of 16 experts held, 6 experts a token
    assert c["moe_assignments"] == 2 * 40 * 6 * 2 * steps
    assert 0.1 < c["moe_held_rows"] / c["moe_assignments"] < 0.5
    assert c["moe_load_max_over_mean"] >= 1.0
    assert c["gmm_fwd_flops"] == 3 * 2 * c["moe_held_rows"] * 32 * 16


def test_held_rows_drift_is_a_share_of_the_uniform_share(sound):
    from benchmarks import train_deepseek_v3 as T

    # a layer that loses a uniform router's whole share, or gains it,
    # reads 1, wherever it started
    assert T.rows_drift([12288, 9000], [12288, 9000 - 12288 / 2],
                        12288) == pytest.approx(0.5)
    assert T.rows_drift([8476, 12000], [14033, 12000], 12288) == (
        pytest.approx(0.4522, abs=1e-4))
    assert T.rows_drift([100, 100], [100, 100], 120) == 0.0
    line, run = sound
    # 2 x 40 tokens, 6 experts a token, 4 of 16 held: 120 rows a layer
    assert work.routed_rows_per_token(run.config) * 80 == 120
    assert 0 <= line["checks"]["held_rows_drift"]["value"] < 0.5


def test_runner_reports_its_metrics_from_its_counts(sound):
    """On the chip the metrics read these counts; here, with peaks
    supplied by hand, the reducers give numbers and none raises."""
    _, run = sound
    r = R.Readings(run.series, run.counts, {"config": run.config,
                                            "chips": 1}, PEAKS)
    assert R.reduce_metric(_spec("train_step.mfu"), r) > 0
    assert R.reduce_metric(_spec("train_step.input_wait_ms"), r) >= 0
    assert R.reduce_metric(_spec("train_tokens_per_s_per_chip"), r) > 0
    share = R.reduce_metric(_spec("moe.held_assignment_share.kanana2"), r)
    assert 10 < share < 50
    assert R.reduce_metric(_spec("moe.expert_load_imbalance.kanana2"),
                           r) >= 1.0
    # no trace: the five kernel metrics are left out, not raised
    for m in NEW_METRICS[:5]:
        assert R.reduce_metric(_spec(m), r) is None


@pytest.mark.parametrize("what", [
    "control_fp8", "fault_unchanged_state", "fault_half_batch",
    "fault_routed_experts_left_out", "fault_rotary_score_left_out"])
def test_each_control_and_fault_comes_out_not_correct(sound, what):
    """The reference in the program's place with one control or fault
    planted, judged by the harness's own comparison as
    `train_deepseek_v3.py limits` judges it on the chip."""
    from benchmarks import train_deepseek_v3 as T

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


def test_a_program_that_drops_the_rotary_score_is_not_correct(monkeypatch):
    """This model's own fault planted in the program and not in the
    reference: q_rope . k_rope left out of every score."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.pallas import flash_attention as fa

    real = fa.mla_attention

    def no_rope(q_nope, q_rope, k_nope, k_rope, v, **kw):
        return real(q_nope, jnp.zeros_like(q_rope), k_nope, k_rope, v, **kw)

    monkeypatch.setattr(fa, "mla_attention", no_rope)
    line, _ = _run()
    assert not line["correct"], line["checks"]


def test_a_program_that_leaves_its_routed_experts_out_is_not_correct(
        monkeypatch):
    import paddle_tpu.ops as F

    real = F.moe_held_experts
    monkeypatch.setattr(
        F, "moe_held_experts", lambda x, *a, **kw: real(x, *a, **kw) * 0.0)
    line, _ = _run()
    assert not line["correct"], line["checks"]


def test_limits_entry_point_is_this_runners_own():
    """prove.py sends every runner not called `train` down the serving
    branch; the configuration's runner has its own `limits`."""
    from benchmarks import train_deepseek_v3 as T

    assert published()["runner"] == "train_deepseek_v3"
    assert [w for w, _ in T.CONTROLS] == [
        "control_fp8", "fault_unchanged_state", "fault_half_batch",
        "fault_routed_experts_left_out", "fault_rotary_score_left_out"]
    assert callable(T.limits) and callable(T.main)


# ------------------------------------------------------- the configuration
def _catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return next(r for r in rows
                if r["name"] == "kanana-2-30b-a3b-instruct-2601")


# the catalog row's `config`, copied here so that the test holds where the
# catalog is not installed (where it is, the two are compared)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


def test_the_copy_of_the_catalog_row_is_the_catalog_row():
    row = _catalog_row()
    if row is None:
        pytest.skip("the catalog is not installed here")
    assert row["config"] == CATALOG
    assert row["source_url"] == published()["source"]


def test_configuration_holds_the_sources_keys_and_states_its_cut():
    cfg = published()
    for key, value in CATALOG.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    # the floors: the leading dense layer once and at least four expert
    # layers, at least 8 experts, at least an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 16, 128256 // 8)
    assert work.layer_kinds(cfg) == (1, 5)
    assert cfg["expert_parallel"] == {"ranks": 8, "rank": 0}
    assert cfg["vocab_parallel"] == {"slices": 8}
    assert (cfg["expert_parallel"]["ranks"] * cfg["n_routed_experts"]
            == CATALOG["n_routed_experts"])
    # no width is cut
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "num_attention_heads", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "rope_theta"):
        assert key not in cfg["reduced"] and cfg[key] == CATALOG[key]
    for key in ("multi_token_prediction", "selection_bias",
                "router_balance_term", "weights", "initializer_range",
                "parameter_dtype", "optimizer", "batch_per_replica"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"loss3_gap", "grad1_worst_leaf_gap",
                                  "grad1_median_leaf_gap",
                                  "change_worst_leaf_gap", "held_rows_drift"}
    assert cfg["limits_from"] and "8 chips" in cfg["stands_for"]
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
    assert block("model.layers.0.self_attn.") == 26_345_984
    assert block("model.layers.0.") == 64_098_816
    # an expert layer outside its experts (the issue's 36,049,536 counts
    # the selection bias, a buffer of 128 float32, as parameters)
    assert block("model.layers.1.", skip=("experts.w_",)) == 36_049_408
    assert sizes["model.layers.1.mlp.experts.w_gate"] * 3 == 16 * 4_718_592
    assert sizes["model.embed_tokens.weight"] == 16032 * 2048
    assert sum(sizes.values()) == 687_502_336
    # 14 B a parameter resident: 9.63 GB, 60 % of the chip's 16 GB
    assert 14 * sum(sizes.values()) == pytest.approx(9.625e9, rel=1e-3)


# ------------------------------------------------ required work, hand-worked
def test_matmul_params_hand_worked():
    cfg = published()
    # q 2048 x 32 x 192, kv_a 2048 x 576, kv_b 512 x 32 x 256, o 4096 x 2048
    assert work.attention_matmul_params(cfg) == (
        2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048)
    assert work.attention_core_flops_per_key(cfg) == 32 * (2 * 192 + 2 * 128)
    assert work.dense_matmul_params(cfg) == 3 * 2048 * 6144
    assert work.routed_rows_per_token(cfg) == 6 * 16 / 128
    # the router's 128 outputs, the shared experts' 1536, 0.75 routed
    # experts of 768
    assert work.moe_matmul_params(cfg) == (
        2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768)


def test_training_flops_per_token_hand_worked():
    cfg = published()
    core = 6 * 20480 * 8193 / 2
    matmuls = 2 * (6 * 26_345_472 + 37_748_736 + 5 * 13_238_272
                   + 2048 * 16032)
    assert work.forward_flops_per_token(cfg, 8192) == pytest.approx(
        core + matmuls)
    total = work.train_flops_per_token(cfg, 8192)
    assert total == pytest.approx(3 * (core + matmuls))
    # 3.28 GFLOP a token, 53.7 TFLOP a step of 16,384; the MLA kernels
    # are 46 % of it at 8k (30 % at 4k: why the cell is at 8k)
    assert total == pytest.approx(3.279e9, rel=1e-3)
    assert total * 16384 == pytest.approx(53.7e12, rel=2e-3)
    assert 3 * core / total == pytest.approx(0.4605, abs=1e-3)
    short = work.train_flops_per_token(cfg, 4096)
    assert 3 * 6 * 20480 * 4097 / 2 / short == pytest.approx(0.299, abs=2e-3)


def test_kernel_work_hand_worked():
    cfg = published()
    ops, nbytes = work.attention_core_work(cfg, 2, 8192)
    assert ops == 6 * 16384 * 20480 * 8193 / 2
    # a token and layer: 32 heads x (q 192, k_nope 128, v 128, o 128 in
    # bf16 and a float32 logsumexp) and the one rotary key: 37,120 B
    assert nbytes == 6 * 16384 * (32 * (2 * 576 + 4) + 128)
    assert nbytes / (6 * 16384) == 37_120
    # compute-bound on a v5e at 8k: 41.9 ms of operations, 4.5 of bytes
    assert ops / PEAKS["bf16_flops"] > 9 * nbytes / PEAKS["hbm_bytes_per_s"]
    bops, bbytes = work.attention_core_work(cfg, 2, 8192, backward=True)
    assert bops == 2 * ops
    assert bbytes == 2 * nbytes + 6 * 16384 * 32 * 4 * 128
    ops, nbytes = work.grouped_matmul_work(cfg, 12288, 5)
    assert ops == 3 * 2 * 12288 * 2048 * 768
    assert nbytes == 5 * 2 * 3 * 16 * 2048 * 768 + 2 * 12288 * (
        2 * (2048 + 768) + 768 + 2048)
    assert work.grouped_matmul_work(cfg, 12288, 5, backward=True) == (
        2 * ops, 2 * nbytes)


# ------------------------------------------- kernel files against instructions
def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _call(name, operands="%bitcast.4"):
    return (f"%{name} = bf16[64,8192,128]{{2,1,0}} custom-call({operands}), "
            'custom_call_target="tpu_custom_call"')


def _trace():
    """A step of 100 ms, busy throughout: the MLA forward 2 x 10 ms (the
    layer is rematerialised), its backward 12 + 13 ms, the grouped
    matmuls 2 + 1 + 2 ms, a flash kernel of another model, and a
    bystander that names a kernel among its operands."""
    ops = [
        (0 * ms, 10 * ms, _call("mla_attention_fwd.12"), ""),
        (10 * ms, 20 * ms, _call("mla_attention_fwd.18"), ""),
        (20 * ms, 32 * ms, _call("mla_attention_bwd_dq.6"), ""),
        (32 * ms, 45 * ms, _call("mla_attention_bwd_dkv.6"), ""),
        (45 * ms, 46 * ms, "%fusion.7 = bf16[2,8192,4096] fusion(bf16[64,"
         "8192,128] %mla_attention_fwd.12), kind=kLoop", ""),
        (46 * ms, 48 * ms, _call("grouped_matmul.3"), ""),
        (48 * ms, 49 * ms, _call("grouped_matmul_dlhs.1"), ""),
        (49 * ms, 51 * ms, _call("grouped_matmul_drhs.1"), ""),
        (51 * ms, 55 * ms, _call("flash_attention_fwd.2"), ""),
        (55 * ms, 100 * ms, "%convert_select_fusion.5 = bf16[8192,12288] "
         "fusion(bf16[8192] %copy-done.9), kind=kOutput", ""),
    ]
    lines = {0: {"ops": ops, "modules": [(0.0, 100 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": published()},
                      PEAKS, trace=trace)


WORK = {"mla_fwd_flops": 197e12 * 4e-3, "mla_fwd_bytes": 819e9 * 1e-3,
        "mla_bwd_flops": 197e12 * 8e-3, "mla_bwd_bytes": 819e9 * 2e-3,
        "gmm_fwd_flops": 197e12 * 0.5e-3, "gmm_fwd_bytes": 819e9 * 0.2e-3,
        "gmm_bwd_flops": 197e12 * 1e-3, "gmm_bwd_bytes": 819e9 * 0.4e-3}


def test_new_kernels_are_found_by_their_own_names():
    r = _readings(_trace(), **WORK)
    value = lambda m: R.reduce_metric(_spec(m), r)
    assert value("kernels.mla_attention.time_share") == pytest.approx(
        10 + 10 + 12 + 13)
    # compute-bound (4 ms of operations against 1 of bytes), over the
    # forward's two calls; the backward over both its kernels
    assert value("kernels.mla_attention_fwd_roofline") == pytest.approx(
        100 * 4 / 20)
    assert value("kernels.mla_attention_bwd_roofline") == pytest.approx(
        100 * 8 / 25)
    assert value("kernels.grouped_matmul.time_share.kanana2") == (
        pytest.approx(5))
    assert value("kernels.grouped_matmul_roofline.kanana2") == (
        pytest.approx(100 * 1.5 / 5))


@pytest.mark.parametrize("kernel,matches,not_matches", [
    ("mla_attention_fwd",
     ["%mla_attention_fwd.12 = ", "%jvp_mla_attention_fwd_.1 = "],
     ["%mla_attention_bwd_dq.6 = ", "%flash_attention_fwd.2 = ",
      "%fusion.7 = f32[1] fusion(%mla_attention_fwd.12)"]),
    ("mla_attention_bwd",
     ["%mla_attention_bwd_dq.6 = ", "%mla_attention_bwd_dkv.6 = "],
     ["%mla_attention_fwd.12 = ", "%flash_attention_bwd_dq.1 = "]),
    ("mla_attention",
     ["%mla_attention_fwd.12 = ", "%mla_attention_bwd_dq.6 = ",
      "%mla_attention_bwd_dkv.6 = "],
     ["%convert.1 = f32[2] convert(%mla_attention_bwd_dq.6)",
      "%flash_attention_fwd.2 = "]),
])
def test_kernel_patterns_are_anchored_at_the_instruction(kernel, matches,
                                                         not_matches):
    k = R._kernel(kernel)
    assert re.search(k.PROGRAMS, "jit_staged")
    for text in matches:
        assert re.search(k.OPS, text), text
    for text in not_matches:
        assert not re.search(k.OPS, text), text
    # the flash kernels' own anchored patterns do not take the MLA kernels
    for flash in ("flash_attention_fwd", "flash_attention_bwd"):
        for text in matches:
            assert not re.search(R._kernel(flash).OPS, text)


def test_a_program_without_the_kernels_reads_nothing():
    """The parent commit has no such kernels and records no such counts:
    every new metric is left out of its line, none raises."""
    ops = [(0.0, 50 * ms, "%fusion.1 = bf16[8] fusion(%p.1)", "")]
    bare = R.summarize_events(
        {0: {"ops": ops, "modules": [(0.0, 50 * ms, "jit_staged")]}}, [],
        set())
    for readings in (_readings(bare, **WORK), _readings()):
        for name in NEW_METRICS[:5]:
            assert R.reduce_metric(_spec(name), readings) is None, name
    for name in NEW_METRICS[5:]:
        assert R.reduce_metric(_spec(name), _readings(bare)) is None, name
    # the kernels without the counts: the shares of a roofline are left
    # out, a time share needs no count
    for name in NEW_METRICS[:5]:
        value = R.reduce_metric(_spec(name), _readings(_trace()))
        assert (value is None) == ("roofline" in name), name


def test_new_metrics_have_their_entries_and_files():
    """By membership, never by position: the next configuration goes in
    after this one without an edit here."""
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in NEW_METRICS:
        m = entries[name]
        assert CELL in m["workloads"]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert set(_spec(name)) == {"reducer", "args", "reads"}
        if name.startswith("kernels."):
            assert (m["unit"], m["source"], m["layer"]) == (
                "%", "device_trace", "kernels")
            assert m["better"] == (
                "higher" if "roofline" in name else "lower")
        else:
            assert (m["source"], m["layer"]) == (
                "program_counter", "expert layer")
    # the doubles read what the entries they double read
    for name in NEW_METRICS[3:]:
        twin = _spec(name[:-len(".kanana2")])
        assert (_spec(name)["reducer"], _spec(name)["args"]) == (
            twin["reducer"], twin["args"])
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
                        "train_step.mfu", "train_step.input_wait_ms",
                        *NEW_METRICS}
    for group, name in (("end_to_end", "train_tokens_per_s_per_chip"),
                        ("per_layer", "train_step.mfu"),
                        ("per_layer", "train_step.input_wait_ms")):
        entry = next(m for m in MANIFEST[group] if m["name"] == name)
        assert CELL in entry["workloads"]
    # no four-chip cell was added, and no name is used twice
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert len(names) == len(set(names))
    assert len(entries) == len(MANIFEST["per_layer"])
