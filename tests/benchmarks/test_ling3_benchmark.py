"""The Ling-3.0-flash configuration's benchmark files on the CPU at a tiny
size: the configuration against the catalog row, the leaf list against the
model's creation order, the parameter count, the runner's control flow
with the program against the plain reference (each step's loss, every
leaf's first gradient, every leaf's change after three AdamW steps), the
control and this model's own planted faults coming out not correct, the
arithmetic of work_ling3.py against hand-worked values, and the new kernel
files' patterns against instruction texts.

Entries of BENCHMARK.json are found by name and membership (`CELL in
entry["workloads"]`), never by position.
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
from benchmarks import weights_ling3 as W                 # noqa: E402
from benchmarks import work_ling3 as work                 # noqa: E402

MANIFEST = run_mod.load(ROOT, "BENCHMARK.json")
CELL = "ling3flash.pretrain-8k"
CONFIG = "ling-3.0-flash-train1"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
# metric files this configuration brings; their entries wait for the
# manifest's set-up metrics to be found by membership (PERF.md section 7)
NEW_METRICS = ("kernels.kda_fwd_roofline", "kernels.kda_bwd_roofline",
               "kernels.kda.time_share", "moe.held_group_share")
ms = 1e-3


@pytest.fixture(autouse=True)
def _own_cache_root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def published():
    return run_mod.load(ROOT, "benchmarks", "configs", CONFIG + ".json")


def tiny():
    """Published layers 0 (dense, KDA) and 11 (an expert layer, MLA) at
    toy widths, the second of four expert-parallel ranks over 4 groups of 4
    experts, float32 (the program and the reference then agree to
    rounding; what bf16 does at the published widths is the chip's
    business)."""
    cfg = published()
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               moe_shared_expert_intermediate_size=16, num_attention_heads=2,
               head_dim=16, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, vocab_size=256,
               num_experts=4, num_experts_per_tok=4, n_group=4, topk_group=2,
               num_hidden_layers=2, kept_layers=[0, 11],
               initializer_range=0.3, max_position_embeddings=256,
               torch_dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=16)
    cfg["expert_parallel"] = {"ranks": 4, "rank": 1}
    cfg["train"] = dict(cfg["train"], batch_per_replica=2,
                        fused_loss_chunk=32)
    # limits of this size and dtype: above what the sound program reads
    # here, below the control and every fault
    cfg["limits"] = {"loss3_gap": 2e-5, "grad1_worst_leaf_gap": 2e-3,
                     "grad1_median_leaf_gap": 1e-4,
                     "change_worst_leaf_gap": 2e-3, "held_rows_drift": 0.5}
    traffic = run_mod.load(ROOT, "benchmarks", "traffic", "pretrain-8k.json")
    traffic.update(seq_len=40, rows=4096)
    cell = {"name": "tiny.ling3", "config": "tiny",
            "traffic": "pretrain-8k", "chips": 1}
    return cell, cfg, traffic


TINY_MANIFEST = {
    "end_to_end": [{"name": "setup_s", "unit": "s"},
                   {"name": "train_tokens_per_s_per_chip",
                    "unit": "tokens/s"}],
    "per_layer": [{"name": "train_step.mfu", "unit": "%"}]}
SEED = 2**31 + 23


def _sound():
    """One run of the runner: (line, Run)."""
    return run_mod.run_cell(TINY_MANIFEST, *tiny(), SEED, 1.0, 0,
                            require_chip=False)


# ------------------------------------------------- model against reference
def test_seeded_leaves_have_their_kinds():
    _, cfg, _ = tiny()
    made, other = W.make_weights(cfg, 5), W.make_weights(cfg, 6)
    key, std = W.seed_key(5), cfg["initializer_range"]
    for i, (name, shape, kind) in enumerate(W.leaf_specs(cfg)):
        a = np.asarray(made[name].astype("float32"))
        assert a.shape == shape
        if i % 7 == 0:      # one leaf in seven: each is its own compile
            one = W.make_leaf(key, index=i, shape=shape, kind=kind, std=std,
                              dtype=made[name].dtype)
            np.testing.assert_array_equal(a, np.asarray(one))
        if kind == "ones":
            assert (a == 1).all() and "norm" in name
        elif kind == "zeros":
            assert (a == 0).all() and name.split(".")[-1] in (
                "A_log", "dt_bias")
        else:
            assert (a != np.asarray(other[name].astype("float32"))).any()


# ----------------------------------------------------------------- the runner
def test_runner_follows_the_control_flow_and_agrees_with_the_reference():
    """One run of the runner at the tiny size (one run: it is the file's
    dearest part). Then, seeded weights and three AdamW steps: each
    step's loss, every leaf's first gradient and every leaf's change
    against the reference, not the worst leaf alone."""
    line, run = _sound()
    json.dumps(line)
    assert list(line)[-1] == "checks"
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
    assert c["steps"] == steps and c["tokens_per_chip"] == 2 * 40 * steps
    assert c["required_flops"] == pytest.approx(
        work.train_flops_per_token(run.config, 40) * 2 * 40 * steps)
    ops, nbytes = work.kda_core_work(run.config, 2 * steps, 40)
    assert (c["kda_fwd_flops"], c["kda_fwd_bytes"]) == (ops, nbytes)
    assert c["kda_bwd_flops"] == 2 * ops
    # one expert layer, 4 of 16 experts held, 4 experts a token
    assert c["moe_assignments"] == 2 * 40 * 4 * steps
    assert c["gmm_fwd_flops"] == 3 * 2 * c["moe_held_rows"] * 32 * 16
    # each of 80 tokens keeps 2 of 4 groups; the held experts' group
    # (group 1) is kept by some and not by all
    assert c["moe_routed_tokens"] == 80 * steps
    assert 0 < c["moe_held_group_tokens"] < c["moe_routed_tokens"]
    r = R.Readings(run.series, c, {"config": run.config, "chips": 1}, PEAKS)
    share = R.reduce_metric(_spec("moe.held_group_share"), r)
    assert share == pytest.approx(
        100 * c["moe_held_group_tokens"] / c["moe_routed_tokens"])
    assert R.reduce_metric(_spec("train_step.mfu"), r) > 0
    # no trace: the kernel metrics are left out, not raised
    for m in NEW_METRICS[:3]:
        assert R.reduce_metric(_spec(m), r) is None
    got, ref = run.kept["got"], run.kept["ref"]
    for a, b in zip(got["losses"], ref["losses"]):
        assert a == pytest.approx(b, rel=2e-6)
    assert set(got["grad_norms"]) == {n for n, _, _ in W.leaf_specs(
        run.config)}
    for key, tol in (("grad_norms", 1e-3), ("change_norms", 1e-3)):
        median = statistics.median(ref[key].values())
        for name, want in ref[key].items():
            gap = abs(got[key][name] - want) / max(want, median)
            assert gap < tol, (key, name, got[key][name], want)
    assert min(ref["grad_norms"].values()) > 0


def test_the_held_group_is_the_held_experts_one():
    from benchmarks import train_ling3 as T

    _, cfg, _ = tiny()
    assert T.held_group(cfg) == 1
    assert T.held_group(published()) == 0
    with pytest.raises(ValueError, match="two groups"):
        T.held_group(dict(cfg, expert_parallel={"ranks": 8, "rank": 1},
                          num_experts=3))


def test_this_models_faults_come_out_not_correct():
    """The reference in the program's place with one of the two faults of
    this model's own mechanisms planted (a decay a head, routing without
    the group limit), against the sound reference on the batches the
    runner feeds, judged by the harness's own comparison as
    `train_ling3.py limits` judges it on the chip, where the fp8 control
    and the other faults are judged too."""
    from benchmarks import train_ling3 as T
    from benchmarks.reference import ling3 as reference
    from benchmarks.train import followed_batches

    cell, cfg, traffic = tiny()
    fed = followed_batches(cfg, traffic, SEED)
    opt = cfg["train"]["optimizer"]
    ref = reference.train_steps(cfg, SEED, fed, opt,
                                dtype=cfg["torch_dtype"])
    same = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
    T.compare(same, ref, ref, cfg["limits"])
    assert same.correct()
    for what in ("fault_decay_head_mean", "fault_group_limit_dropped"):
        got = T.control_readings(cfg, SEED, fed, what)
        judged = run_mod.Run(cell, cfg, traffic, SEED, 0.0, False)
        T.compare(judged, got, ref, cfg["limits"])
        assert not judged.correct(), (what, judged.checks)


def test_limits_entry_point_is_this_runners_own():
    from benchmarks import train_ling3 as T

    assert published()["runner"] == "train_ling3"
    assert [w for w, _ in T.CONTROLS] == [
        "control_fp8", "fault_unchanged_state", "fault_half_batch",
        "fault_decay_head_mean", "fault_group_limit_dropped",
        "fault_held_experts_left_out"]
    assert callable(T.limits) and callable(T.main)


# ------------------------------------------------------- the configuration
# the source's published config.json, every key, as the catalog copies it
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2560, "intermediate_size": 6144, "kda_lower_bound": -5,
    "kda_safe_gate": True, "kv_lora_rank": 512, "layer_group_size": 6,
    "linear_silu": True, "max_position_embeddings": 262144,
    "max_window_layers": 20, "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768, "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False, "n_group": 8, "no_kda_lora": True,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 512,
    "num_experts_per_tok": 8, "num_hidden_layers": 42,
    "num_key_value_heads": 32, "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "partial_rotary_factor": 0.5, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 6000000,
    "rotary_dim": 64, "routed_scaling_factor": 2.5,
    "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4, "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "up_proj_norm": False,
    "use_bias": False, "use_kda_lora": False, "use_mla_nope": False,
    "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
    "v_head_dim": 128, "value_norm": False, "vocab_size": 157184,
    "model_type": "bailing_hybrid"}


def test_configuration_holds_the_sources_keys_and_states_its_cut():
    cfg = published()
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["published"][key] == value
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["reduced"] == reduced
    # one dense layer and one whole period of six; 8 experts of 512 held
    # (64 ranks); an eighth of the vocabulary
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (7, 8, 157184 // 8)
    assert cfg["kept_layers"] == [0, 6, 7, 8, 9, 10, 11]
    assert work.layer_kinds(cfg) == (6, 1, 1, 6)
    assert cfg["expert_parallel"] == {"ranks": 64, "rank": 0}
    assert cfg["vocab_parallel"] == {"slices": 8}
    assert (cfg["expert_parallel"]["ranks"] * cfg["num_experts"]
            == CATALOG["num_experts"])
    for key in ("layer_pattern", "kda_gate", "kda_qk", "output_gates",
                "qk_norm", "left_out", "selection_bias", "weights",
                "initializer_range", "parameter_dtype", "optimizer",
                "batch_per_replica", "recompute", "held_rows"):
        assert key in cfg["assumed"], key
    assert set(cfg["limits"]) == {"loss3_gap", "grad1_worst_leaf_gap",
                                  "grad1_median_leaf_gap",
                                  "change_worst_leaf_gap", "held_rows_drift"}
    assert cfg["limits_from"] and "64 chips" in cfg["stands_for"]
    tr = cfg["train"]
    assert tr["batch_per_replica"] in (1, 2) and tr["recompute"] is True
    assert tr["optimizer"]["warmup_steps"] == 2000


def test_parameter_count_is_reckoned_from_the_leaf_list():
    cfg = published()
    sizes = {n: int(np.prod(s)) for n, s, _ in W.leaf_specs(cfg)}
    block = lambda prefix: sum(v for n, v in sizes.items()
                               if n.startswith(prefix))
    assert block("model.layers.1.attention.") == 52_646_048
    assert block("model.layers.6.attention.") == 31_966_080
    assert block("model.layers.1.mlp.") == 54_394_880
    assert block("model.layers.0.mlp.") == 47_185_920
    assert block("model.layers.0.") == 99_837_088
    assert block("model.layers.1.") == 107_046_048
    assert block("model.layers.6.") == 86_366_080
    assert sizes["model.embed_tokens.weight"] == 19648 * 2560
    assert sum(sizes.values()) == 822_033_728
    # 14 B a parameter resident (bf16 weight, fp32 master, two moments)
    assert 14 * sum(sizes.values()) == pytest.approx(11.51e9, rel=1e-3)


# ------------------------------------------------ required work, hand-worked
def test_training_flops_per_token_hand_worked():
    cfg = published()
    kda = 2 * (5 * 2560 * 4096 + 2 * 2560 * 32) + 6 * 32 * 128 * 128
    mla = 2 * (2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560
               + 2560 * 32) + 32 * 640 * 8193 / 2
    dense = 2 * 3 * 2560 * 6144
    moe = 2 * (2560 * 512 + 3 * 2560 * 768 + 8 * 8 / 512 * 3 * 2560 * 768)
    head = 2 * 2560 * 19648
    fwd = 6 * kda + mla + dense + 6 * moe + head
    assert work.forward_flops_per_token(cfg, 8192) == pytest.approx(fwd)
    # 1.088 GFLOP a token forward; 53.5 TFLOP a step of 2 x 8192
    assert fwd == pytest.approx(1.088e9, rel=1e-3)
    assert work.train_flops_per_token(cfg, 8192) * 16384 == pytest.approx(
        53.48e12, rel=1e-3)


def test_kernel_work_hand_worked():
    cfg = published()
    ops, nbytes = work.kda_core_work(cfg, 2, 8192)
    assert ops == 6 * 16384 * 6 * 32 * 128 * 128
    # a token and layer: q, k, v, o in bf16, g in float32, beta
    assert nbytes == 6 * 16384 * (4 * 2 * 4096 + 4 * 4096 + 4 * 32)
    # memory-bound on a v5e: 5.9 ms of bytes, 1.6 of operations
    assert nbytes / PEAKS["hbm_bytes_per_s"] > 3 * ops / PEAKS["bf16_flops"]
    bops, bbytes = work.kda_core_work(cfg, 2, 8192, backward=True)
    assert (bops, bbytes) == (2 * ops, 2 * nbytes)
    ops, nbytes = work.grouped_matmul_work(cfg, 2048, 6)
    assert ops == 3 * 2 * 2048 * 2560 * 768
    assert nbytes == 6 * 2 * 3 * 8 * 2560 * 768 + 2 * 2048 * (
        2 * (2560 + 768) + 768 + 2560)


# ------------------------------------------- kernel files against instructions
def _spec(metric):
    return run_mod.load(ROOT, "benchmarks", "metrics", metric + ".json")


def _call(name):
    return (f"%{name} = bf16[2,8192,4096]{{2,1,0}} custom-call(%p.1), "
            'custom_call_target="tpu_custom_call"')


def _trace():
    """A step of 100 ms, busy throughout: the forward 6 x 2 + 6 x 2 ms
    (recomputed), the backward 6 x 5 ms, a delta-rule kernel that is not
    KDA's, and a bystander naming a KDA kernel among its operands."""
    ops, at = [], 0.0
    for i in range(6):
        for name, dur in ((f"jvp_kda_fwd_.{i}", 2), (f"kda_fwd.{i}", 2),
                          (f"transpose_jvp_kda_bwd_.{i}", 5)):
            ops.append((at * ms, (at + dur) * ms, _call(name), ""))
            at += dur
    ops.append((at * ms, (at + 4) * ms, _call("gated_delta_rule_fwd.1"), ""))
    at += 4
    ops.append((at * ms, 100 * ms, "%fusion.7 = bf16[2,8192,4096] fusion("
                "bf16[2,8192,4096] %kda_fwd.1), kind=kLoop", ""))
    lines = {0: {"ops": ops, "modules": [(0.0, 100 * ms, "jit_staged")]}}
    return R.summarize_events(lines, [], set())


def _readings(trace=None, **counts):
    return R.Readings({}, counts, {"chips": 1, "config": published()},
                      PEAKS, trace=trace)


WORK = {"kda_fwd_flops": 197e12 * 1e-3, "kda_fwd_bytes": 819e9 * 6e-3,
        "kda_bwd_flops": 197e12 * 2e-3, "kda_bwd_bytes": 819e9 * 12e-3}


def test_new_kernels_are_found_by_their_own_names():
    r = _readings(_trace(), **WORK)
    value = lambda m: R.reduce_metric(_spec(m), r)
    assert value("kernels.kda.time_share") == pytest.approx(6 * 9)
    assert value("kernels.kda_fwd_roofline") == pytest.approx(100 * 6 / 24)
    assert value("kernels.kda_bwd_roofline") == pytest.approx(100 * 12 / 30)


@pytest.mark.parametrize("kernel,matches,not_matches", [
    ("kda_fwd", ["%kda_fwd.12 = ", "%jvp_kda_fwd_.1 = "],
     ["%transpose_jvp_kda_bwd_.6 = ", "%gated_delta_rule_fwd.2 = ",
      "%fusion.7 = f32[1] fusion(%kda_fwd.12)"]),
    ("kda_bwd", ["%transpose_jvp_kda_bwd_.6 = ", "%kda_bwd.1 = "],
     ["%kda_fwd.12 = ", "%gated_delta_rule_bwd.1 = "]),
    ("kda", ["%kda_fwd.12 = ", "%transpose_jvp_kda_bwd_.6 = "],
     ["%convert.1 = f32[2] convert(%kda_bwd.6)",
      "%gated_delta_rule_fwd.2 = "]),
])
def test_kernel_patterns_are_anchored_and_apart_from_the_delta_rules(
        kernel, matches, not_matches):
    k = R._kernel(kernel)
    assert re.search(k.PROGRAMS, "jit_staged")
    for text in matches:
        assert re.search(k.OPS, text), text
        # the accepted delta-rule patterns do not take KDA's kernels
        for gdr in ("gated_delta_rule_fwd", "gated_delta_rule_bwd",
                    "gated_delta_rule"):
            assert not re.search(R._kernel(gdr).OPS, text)
    for text in not_matches:
        assert not re.search(k.OPS, text), text


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


def test_the_cell_has_its_entries_and_its_metric_files():
    """By name and membership. The cell reports the two training metrics
    every training cell reports; the new metrics' files stand ready, and
    their entries are left out of the manifest (an entry after the ten
    set-up metrics is what the accepted test_startup_metrics.py refuses,
    and one before them would move what is there: PERF.md section 7)."""
    for name in NEW_METRICS:
        spec = _spec(name)
        assert set(spec) == {"reducer", "args", "reads"}
        assert spec["reducer"] in R.REDUCERS
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
    assert not set(NEW_METRICS) & {m["name"] for m in MANIFEST["per_layer"]}
    names = [w["name"] for w in MANIFEST["workloads"]]
    assert len(names) == len(set(names))
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 0
